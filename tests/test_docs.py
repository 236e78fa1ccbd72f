"""The user documents against the program they describe.

Three things a document can get wrong without any other test noticing:

* an option the README's tables list that the parser does not have
  (each row of every `| \\`--flag\\` | default | effect |` table is a
  case, read from `README.md`; the parser is the one `main` uses);
* a file a document sends its reader to that is not in the tree — the
  way `bench.py` and the old runtime's records were named long after
  nothing read them. A backticked token counts as a path
  when it is made of path characters only and either ends in a known
  suffix or starts with a directory of the repo or the package (so
  `telem/drops`, a store field, and `Net/Net1/Net2` are not paths); it
  must exist (from the repo root, the package or the document's own
  directory; globs may match), or — a bare file name —
  be a name the program's own source holds as a string, i.e. a file a
  run writes (`chaos_soak.json`). A run-time file under a directory the user
  chooses is written with the placeholder in angle brackets
  (`<dir>/phases.json`), which takes it out of the check. The
  reference's `src/` is not here and is not held to it, with or
  without the prefix (`SURVEY.md` cites its files as `src/<name>`);
  neither are `PERF.md`, `ROADMAP.md` and `CHANGES.md`, which are
  histories;
* a verb that was removed and still dispatches.

No jax work: the parser is built, nothing is parsed into a run.
"""

import functools
import glob
import os
import re

import pytest

smoke = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "federated_pytorch_test_tpu")

USER_DOCUMENTS = (
    "README.md",
    "docs/FAULT.md",
    "docs/MIGRATION.md",
    "docs/OBSERVABILITY.md",
    "docs/PERF.md",
    "docs/SCALE.md",
)
PATH_SUFFIXES = (".py", ".md", ".json", ".sh")


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _readme_flags():
    """The flag of every option-table row, in the README's order."""
    return re.findall(r"^\| `(--[a-z0-9-]+)", _read("README.md"), flags=re.M)


@pytest.fixture(scope="module")
def option_strings():
    from federated_pytorch_test_tpu.__main__ import _build_parser

    return {s for a in _build_parser()._actions for s in a.option_strings}


@smoke
@pytest.mark.parametrize("flag", _readme_flags())
def test_readme_flag_is_a_parser_flag(flag, option_strings):
    assert flag in option_strings, (
        f"README.md's option table lists {flag}, which the argument "
        "parser of `python -m federated_pytorch_test_tpu` does not have"
    )


def test_readme_option_tables_were_found():
    # the collection above reads the README by a pattern: a reformatted
    # table must not turn the check into zero cases silently
    assert len(_readme_flags()) >= 20


# ------------------------------------------------------ paths in documents


@functools.cache
def _tree_dirs():
    """Names a path in a document can start with: the directories of the
    repo's root and of the package."""
    return {
        e.name for root in (REPO, PACKAGE) for e in os.scandir(root)
        if e.is_dir() and not e.name.startswith((".", "__"))
    }


@functools.cache
def _reference_files():
    """File names of the reference's `src/`, as `SURVEY.md` cites them."""
    return set(re.findall(r"src/([A-Za-z0-9_]+\.py)", _read("SURVEY.md")))


def _path_tokens(text):
    """Backticked tokens of `text` that name a file or a directory."""
    out, dirs, reference = [], _tree_dirs(), _reference_files()
    for tok in re.findall(r"`([^`\n]+)`", text):
        tok = tok.split("::")[0]  # tests/test_x.py::test_name
        tok = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", tok)  # file.py:12-34
        if not re.fullmatch(r"[A-Za-z0-9_.*/-]+", tok):
            continue  # commands, placeholders (<stream>), $VARS, globs of words
        if tok.startswith(("-", "/", "src/")) or tok in reference:
            continue  # flags, absolute host paths, the reference's tree
        in_tree = "/" in tok and tok.split("/")[0] in dirs
        if in_tree or tok.endswith(PATH_SUFFIXES):
            out.append(tok)
    return sorted(set(out))


@functools.cache
def _program_source():
    """Every line of source the program is made of, as one string."""
    files = glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True)
    files += [os.path.join(REPO, n) for n in ("chip_smoke.py", "scripts/ci.sh")]
    files += glob.glob(os.path.join(REPO, "chipbench", "**", "*.py"), recursive=True)
    return "\n".join(open(f, encoding="utf-8").read() for f in files)


def _resolves(tok, doc):
    roots = (REPO, PACKAGE, os.path.dirname(os.path.join(REPO, doc)))
    if any(glob.glob(os.path.join(r, tok.rstrip("/"))) for r in roots):
        return True
    # a bare file name the program's own source holds as a string:
    # written by a run
    return "/" not in tok and re.search(
        "[\"']" + re.escape(tok) + "[\"']", _program_source()
    ) is not None


@pytest.mark.parametrize("doc", USER_DOCUMENTS)
def test_user_document_names_only_files_that_exist(doc):
    tokens = _path_tokens(_read(doc))
    assert tokens, f"{doc}: no path found — the token pattern has rotted"
    missing = [t for t in tokens if not _resolves(t, doc)]
    assert not missing, (
        f"{doc} names files that are not in the tree (correct the "
        f"document): {missing}"
    )


# ----------------------------------------------------------- removed verbs


@smoke
@pytest.mark.parametrize("verb", ["trend", "debt"])
def test_removed_verb_is_refused(verb, capsys):
    # the second record of performance went in PR 31 (root PERF.md §6):
    # the word is no verb any more, so it reaches the run's own parser,
    # which refuses it; and obs/ has no `<verb>_main` to dispatch to
    from federated_pytorch_test_tpu import obs
    from federated_pytorch_test_tpu.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main([verb])
    assert exc.value.code not in (0, None)
    assert "unrecognized arguments" in capsys.readouterr().err
    assert not hasattr(obs, f"{verb}_main")
