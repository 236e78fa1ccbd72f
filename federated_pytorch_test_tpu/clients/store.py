"""Host-side virtual-client state store: N ≫ K clients, O(C) round cost.

The pre-cohort engine holds every configured client's state as `[K]`
device arrays — cross-*silo* simulation, where K is bounded by HBM.
Cross-*device* federated learning
inverts the shape: a server keeps state for thousands-to-millions of
mostly-idle virtual clients on the HOST, and each round only the sampled
cohort's rows ever touch a device (clients/cohort.py, engine/trainer.py
gather → fused round → scatter).

`ClientStore` is that host side. Four properties drive the design:

* **Lazy chunks.** Client rows live in fixed-size chunks
  (`chunk_clients` ids per chunk). A chunk is PRISTINE — represented by
  nothing at all — until some row of it is first written; gathers from a
  pristine chunk broadcast the per-field init row (cohort mode requires
  the common-seed init, engine/config.py, so every virtual client starts
  from the same row). Memory and checkpoint cost therefore scale with
  the clients ever *touched*, not with N: a 1M-client store that has run
  ten C=64 cohorts holds ≤ 640 materialized rows.

* **Spilled residency** (`resident_chunks`, docs/SCALE.md §Spilled
  store). Even "touched only" grows without bound over a long run, so
  the RESIDENT set — chunks held in RAM — is LRU-bounded when a budget
  is set. A CLEAN chunk (its current version is on disk) evicts for
  free: the dict entry is dropped and later gathers read the needed
  rows straight off a memory-mapped view of its `.npz` file (rows are
  copied out; the file is never held open past the call). A DIRTY chunk
  spills first — written as the next `chunk_<cid>_v<seq>.npz` version
  through exactly the `save` path, so the following manifest simply
  references the already-written file. Host RSS is therefore
  O(resident budget + cohort), flat in N; with no budget the store
  keeps the legacy keep-everything behavior bit for bit.

* **Dirty-chunk checkpointing.** `save(dir, step)` writes ONLY the
  chunks dirtied since the last save (one `.npz` per chunk, tmp+rename
  like utils/checkpoint.py) plus a small JSON manifest mapping every
  materialized chunk to its current file. The manifest write is the
  atomic commit point: a crash mid-save leaves at worst orphaned chunk
  files that the next save garbage-collects, never a torn snapshot —
  the previous manifest still references the previous versions. Per-loop
  checkpoint delta is O(C) (tests/test_clients.py asserts it), while a
  naive store-in-the-orbax-tree design would rewrite O(N) every loop.
  An eviction-spilled version written between saves is the same story:
  committed only when a manifest names it, orphaned (and GC'd) when the
  run crashes first — spilling never widens the crash window.

* **Field registry.** A row is a set of named fields — `flat` (the
  client's parameter vector), one per batch-stats leaf, one per
  partition group's persistent ADMM rho (`rho/<gid>`, registered lazily
  the first time that group's round completes; see
  engine/trainer.py `_rho_store`), one per group's error-feedback
  residual under a lossy exchange codec (`ef/<gid>`, zero fill —
  `--error-feedback`, exchange/, docs/PERF.md: the compression error a
  client's last encode lost follows the VIRTUAL client into its next
  cohort), and the telemetry reliability counters (`telem/*`,
  docs/SCALE.md). L-BFGS history and the consensus
  y/z duals are deliberately NOT stored: the engine re-initializes them
  fresh at every partition round by construction (utils/checkpoint.py
  module docstring), so persisting them would be dead weight per client.

Static per-client metadata (data-shard assignment, per-shard sample
counts) is computed once at construction and never checkpointed — it is
a pure function of (N, n_shards, shard sizes), the same purity contract
the cohort sampler and fault plans ride.

Thread-safety: the cohort prefetcher (clients/prefetch.py) gathers loop
n+1's rows on a background thread while the trainer's main thread may
scatter loop n's, save a checkpoint, or evict under the residency
budget. One re-entrant lock serializes every public operation — the
critical sections are O(C) row copies or one chunk's file I/O, so the
background gather still overlaps all of the round's device compute.

Storage integrity (docs/SCALE.md §Durability, docs/FAULT.md §Storage):
with `checksums` on (the default) every chunk write stamps a digest
(fault/io.py `checksum`) that is recorded in the manifest and verified
on EVERY read — mmap or full — before any row can reach a gather, and
the manifest itself carries a self-CRC. A failed verification retries
(bounded, exponential backoff — transient rot/injected faults heal on a
clean re-read), then walks the repair ladder: adopt the newest intact
PRIOR version of the chunk (versions are never overwritten, so older
snapshots survive); else re-initialize the chunk pristine by
construction and count it (`repairs_reinit`, surfaced into the
telemetry-weighting penalties); else — with `repair=False`, the strict
resume/scrub stance — refuse loudly naming the chunk. Legacy v1
manifests (no digests) restore read-only-accepted: their chunks simply
go unverified until the next save rewrites them under v2. The optional
`storage_io` shim (fault/io.py StorageFaultShim) routes every chunk
read/write through the chaos schedule of the plan's `storage` axis.
"""

from __future__ import annotations

import ast
import contextlib
import io as _io
import json
import mmap
import os
import struct
import threading
import warnings
import zipfile
from typing import Dict, Optional

import numpy as np

from federated_pytorch_test_tpu.fault.io import (
    CHECKSUM_ALG,
    IntegrityError,
    checksum,
    retry_io,
    stamp_crc,
    verify_crc,
    verify_digest,
)

# version 2 adds per-chunk digests + the manifest self-CRC; version 1
# (pre-integrity) manifests are still restorable — legacy chunks are
# accepted read-only/unverified (module docstring)
_MANIFEST_VERSION = 2


def _manifest_path(root: str, step: int) -> str:
    return os.path.join(root, f"manifest_step_{step}.json")


def _npz_views(buf, zf: zipfile.ZipFile) -> Dict[str, np.ndarray]:
    """Read-only array views into an uncompressed `.npz`'s byte buffer.

    np.savez STORES members uncompressed, so each `<name>.npy` payload
    is a contiguous byte range of the archive: parse each member's
    local header + npy header and view the payload in place — `buf` may
    be an mmap (the zero-copy spilled-gather path) or a verified bytes
    object (the checksummed/shimmed path). Raises on anything
    unexpected; the wrappers below fall back to a full `np.load`.
    """
    out: Dict[str, np.ndarray] = {}
    for info in zf.infolist():
        if info.compress_type != zipfile.ZIP_STORED:
            raise ValueError("compressed npz member")
        if not info.filename.endswith(".npy"):
            continue
        ho = info.header_offset
        # local file header: magic(4) .. name_len@26 extra_len@28
        if buf[ho : ho + 4] != b"PK\x03\x04":
            raise ValueError("unexpected local header")
        name_len, extra_len = struct.unpack_from("<HH", buf, ho + 26)
        o = ho + 30 + name_len + extra_len
        if buf[o : o + 6] != b"\x93NUMPY":
            raise ValueError("not an npy member")
        major = buf[o + 6]
        if major == 1:
            (hlen,) = struct.unpack_from("<H", buf, o + 8)
            data = o + 10 + hlen
            header = bytes(buf[o + 10 : o + 10 + hlen])
        else:
            (hlen,) = struct.unpack_from("<I", buf, o + 8)
            data = o + 12 + hlen
            header = bytes(buf[o + 12 : o + 12 + hlen])
        meta = ast.literal_eval(header.decode("latin1"))
        if meta.get("fortran_order") or not isinstance(
            meta.get("descr"), str
        ):
            raise ValueError("non-C-contiguous or structured npy")
        dtype = np.dtype(meta["descr"])
        shape = tuple(meta["shape"])
        arr = np.ndarray(shape, dtype, buffer=buf, offset=data)
        if arr.flags.writeable:
            arr.flags.writeable = False
        out[info.filename[:-4]] = arr
    return out


def _mmap_npz(path: str) -> Dict[str, np.ndarray]:
    """Read-only array views into an uncompressed `.npz`, one shared mmap.

    `np.load(..., mmap_mode=...)` silently ignores the mode for zip
    archives (every member would be decompressed into RAM), which is
    exactly the O(chunk) copy a spilled gather exists to avoid: map the
    file once and view each member's payload in place (`_npz_views`).
    A gather then copies only the rows it needs.

    Falls back to a full `np.load` read (same values, more RAM for the
    duration of the call) on anything unexpected — compressed members,
    Fortran order, a dtype whose descr isn't a plain string — rather
    than ever failing a restore over an optimization.
    """
    try:
        with open(path, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        with zipfile.ZipFile(path) as zf:
            return _npz_views(mm, zf)
    except (OSError, ValueError, KeyError, SyntaxError, struct.error):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}


def _npz_from_bytes(data: bytes, path: str) -> Dict[str, np.ndarray]:
    """`_mmap_npz`'s equivalent over an in-memory byte buffer (the
    shimmed read path holds the — possibly chaos-corrupted — bytes, not
    the file). Unparseable data raises `IntegrityError` naming the file:
    by the time this runs the buffer either passed its checksum or has
    none to check, so a parse failure IS corruption, and the caller's
    retry/repair ladder must see it as such rather than a crash."""
    try:
        try:
            with zipfile.ZipFile(_io.BytesIO(data)) as zf:
                return _npz_views(data, zf)
        except (ValueError, KeyError, SyntaxError, struct.error,
                zipfile.BadZipFile):
            with np.load(_io.BytesIO(data), allow_pickle=False) as z:
                return {k: z[k] for k in z.files}
    except Exception as e:
        raise IntegrityError(
            f"cannot parse chunk file {path}: {e}", path=path
        ) from e


class ClientStore:
    """Chunked, lazily-materialized `[N, ...]` per-field client state."""

    def __init__(
        self,
        n_virtual: int,
        shard_ids: np.ndarray,
        sample_counts: np.ndarray,
        chunk_clients: int = 256,
        resident_chunks: Optional[int] = None,
        spill_dir: Optional[str] = None,
        checksums: bool = True,
        storage_io=None,
        io_retries: int = 3,
        repair: bool = True,
    ):
        """`resident_chunks` bounds the chunks held in RAM (None = keep
        everything, the legacy behavior); eviction of a dirty chunk
        spills it under `spill_dir` (the same directory later `save`
        calls must use — asserted there), so a budget REQUIRES one.

        `checksums` stamps/verifies per-chunk digests (module
        docstring); `storage_io` is an optional fault/io.py
        StorageFaultShim routing chunk reads/writes through the storage
        chaos axis; `io_retries` bounds the read/write retry;
        `repair=False` makes an unrepairable chunk refuse loudly
        (IntegrityError naming it) instead of re-initializing pristine."""
        if n_virtual < 1:
            raise ValueError(f"n_virtual must be >= 1, got {n_virtual}")
        if chunk_clients < 1:
            raise ValueError(
                f"chunk_clients must be >= 1, got {chunk_clients}"
            )
        if resident_chunks is not None:
            if resident_chunks < 1:
                raise ValueError(
                    f"resident_chunks must be >= 1, got {resident_chunks}"
                )
            if spill_dir is None:
                raise ValueError(
                    "a resident-chunk budget needs a spill_dir: evicting "
                    "a dirty chunk must write its bytes somewhere"
                )
        self.n_virtual = int(n_virtual)
        self.chunk_clients = int(chunk_clients)
        self.resident_chunks = (
            int(resident_chunks) if resident_chunks is not None else None
        )
        self._spill_dir = os.path.abspath(spill_dir) if spill_dir else None
        self.shard_ids = np.asarray(shard_ids, np.int64).reshape(-1)
        self.sample_counts = np.asarray(sample_counts, np.int64).reshape(-1)
        if self.shard_ids.shape[0] != n_virtual:
            raise ValueError(
                f"shard_ids has {self.shard_ids.shape[0]} entries for "
                f"n_virtual={n_virtual}"
            )
        if self.sample_counts.shape[0] != n_virtual:
            raise ValueError(
                f"sample_counts has {self.sample_counts.shape[0]} entries "
                f"for n_virtual={n_virtual}"
            )
        # field name -> [*(row shape)] init row (the pristine value of
        # every client's row of that field)
        self._fills: Dict[str, np.ndarray] = {}
        # chunk id -> {field name -> [rows_in_chunk, *(row shape)]};
        # a chunk dict may lack fields registered after it materialized —
        # those fall back to the fill row on gather. Insertion order IS
        # the LRU order: touches reinsert at the end, eviction pops the
        # front.
        self._chunks: Dict[int, Dict[str, np.ndarray]] = {}
        self._dirty: set = set()
        self._files: Dict[int, str] = {}  # chunk id -> current filename
        self._seq = 0  # monotone version counter for chunk filenames
        # field metadata of a restored manifest: fields saved by the
        # crashed run but not yet re-registered by this one (lazy rho
        # fields) — validated at re-registration time
        self._saved_fields: Dict[str, dict] = {}
        # spilled-store telemetry (obs: `store_summary` / the `memory`
        # record's store block): evictions under the residency budget,
        # bytes the dirty-spill path wrote, chunk-file reads gathers
        # served off disk (cache misses — see _read_chunk)
        self.evictions = 0
        self.spill_bytes = 0
        self.spill_reads = 0
        # host-side row traffic: surfaced via traffic() in the status
        # sidecar's store block so the chaos oracle (and `watch`) can
        # see the cohort data path moving rows
        self.gather_calls = 0
        self.gather_rows = 0
        self.scatter_calls = 0
        self.scatter_rows = 0
        # storage integrity (module docstring): per-file digests the
        # manifest records, the chaos shim, and the detect/heal/repair
        # counters the `integrity` record + scrub report surface
        self.checksums = bool(checksums)
        self._io = storage_io
        self.io_retries = int(io_retries)
        self.repair = bool(repair)
        self._digests: Dict[str, dict] = {}
        self.verified_reads = 0
        self.integrity_failures = 0
        self.retry_heals = 0
        self.repairs_prior = 0
        self.repairs_reinit = 0
        # per-virtual-client repair counts since the last drain
        # (take_repaired): the trainer scatters them into the
        # `telem/repairs` reliability field so telemetry weighting can
        # demote clients whose rows were rebuilt
        self._repaired: Dict[int, int] = {}
        # chunk-file versions some retained MANIFEST references: a
        # spill may delete the version it supersedes only when no
        # manifest names it (resume must reach every retained
        # snapshot); maintained by save()'s GC scan and load()
        self._protected: set = set()
        # parsed mmap views per chunk FILE (versions are immutable, so
        # entries never go stale): one zip parse serves every field of
        # a gather batch instead of fields × chunks parses. Small FIFO
        # bound — mappings are virtual memory, but the handles are not
        # free. Guarded by _lock like everything else.
        self._mmap_cache: Dict[str, Dict[str, np.ndarray]] = {}
        self._mmap_cache_max = 8
        # batched_writes() defers residency enforcement across a
        # multi-field scatter (one eviction sweep per loop, not one per
        # field — re-spilling the same chunk per field would multiply
        # the spill I/O by the field count)
        self._defer_budget = False
        # one lock for every public operation: the cohort prefetcher
        # gathers on a background thread (module docstring)
        self._lock = threading.RLock()

    # ------------------------------------------------------------- fields

    def register_field(self, name: str, fill_row: np.ndarray) -> None:
        """Declare field `name` with its pristine per-client row.

        Idempotent for an identical fill (re-registration happens on
        resume); a *different* fill for an existing name is a caller bug
        and raises — silently changing what pristine clients hold would
        corrupt every never-sampled client.
        """
        row = np.asarray(fill_row)
        with self._lock:
            if name in self._fills:
                if (
                    self._fills[name].shape != row.shape
                    or self._fills[name].dtype != row.dtype
                    or not np.array_equal(
                        self._fills[name], row, equal_nan=True
                    )
                ):
                    raise ValueError(
                        f"field {name!r} re-registered with a different "
                        "fill row (shape/dtype/value mismatch)"
                    )
                return
            saved = self._saved_fields.get(name)
            if saved is not None and (
                list(row.shape) != list(saved["shape"])
                or str(row.dtype) != saved["dtype"]
            ):
                raise ValueError(
                    f"client-store field {name!r} was saved with shape "
                    f"{saved['shape']} dtype {saved['dtype']} but this run "
                    f"registers shape {list(row.shape)} dtype {row.dtype}"
                )
            self._fills[name] = row.copy()

    def has_field(self, name: str) -> bool:
        with self._lock:
            return name in self._fills

    @property
    def fields(self):
        with self._lock:  # the prefetch thread snapshots this while
            # the main thread may be registering a group's first rho/ef
            return tuple(sorted(self._fills))

    @property
    def saved_fields(self) -> Dict[str, dict]:
        """Field metadata a restored manifest recorded (`{name: {shape,
        dtype}}`): what the crashed run had registered at its last save.
        The trainer re-registers its lazy fields (per-group rho) from
        this so restored chunks holding them stay addressable before the
        group's first round of the resumed run."""
        return dict(self._saved_fields)

    # ------------------------------------------------------- gather/scatter

    def _chunk_of(self, vid: int) -> int:
        return int(vid) // self.chunk_clients

    def _chunk_rows(self, cid: int) -> int:
        lo = cid * self.chunk_clients
        return min(self.chunk_clients, self.n_virtual - lo)

    def _check_ids(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size and (ids.min() < 0 or ids.max() >= self.n_virtual):
            raise IndexError(
                f"virtual-client ids out of range [0, {self.n_virtual}): "
                f"min={ids.min()}, max={ids.max()}"
            )
        return ids

    def _by_chunk(self, ids: np.ndarray):
        """`(cid, positions, local_rows)` groups of a checked id vector —
        one entry per touched chunk, positions indexing the caller's
        id/row order (the vectorized replacement for a per-id loop)."""
        cids = ids // self.chunk_clients
        out = []
        for cid in np.unique(cids):
            pos = np.nonzero(cids == cid)[0]
            out.append(
                (int(cid), pos, ids[pos] - int(cid) * self.chunk_clients)
            )
        return out

    def _touch(self, cid: int) -> None:
        """Move a resident chunk to the LRU tail (most recently used)."""
        self._chunks[cid] = self._chunks.pop(cid)

    def gather(self, name: str, ids: np.ndarray) -> np.ndarray:
        """Rows of field `name` for `ids`, as a fresh `[len(ids), ...]`
        array (never a view into the store — the caller device_puts and
        possibly donates it). Non-resident chunks with an on-disk
        version serve their rows off a memory-mapped read without
        rejoining the resident set — a gather never costs RAM beyond
        its own output."""
        with self._lock:
            ids = self._check_ids(ids)
            self.gather_calls += 1
            self.gather_rows += int(ids.size)
            fill = self._fills[name]
            out = np.empty((ids.size,) + fill.shape, fill.dtype)
            for cid, pos, rows in self._by_chunk(ids):
                chunk = self._chunks.get(cid)
                if chunk is not None:
                    self._touch(cid)
                    if name in chunk:
                        out[pos] = chunk[name][rows]
                    else:
                        out[pos] = fill
                elif cid in self._files:
                    arrs = self._read_chunk(cid)
                    if name in arrs:
                        out[pos] = arrs[name][rows]
                    else:
                        # field registered after this version was written
                        out[pos] = fill
                else:
                    out[pos] = fill
            return out

    def scatter(self, name: str, ids: np.ndarray, rows: np.ndarray) -> None:
        """Write `rows[i]` into client `ids[i]`'s slot of field `name`,
        materializing (init-filled or disk-reloaded) chunks as needed
        and marking every touched chunk dirty for the next `save`. The
        residency budget is enforced AFTER the whole scatter — mid-
        operation the resident set may exceed it by up to the cohort's
        chunks (RSS stays O(resident + cohort))."""
        with self._lock:
            ids = self._check_ids(ids)
            self.scatter_calls += 1
            self.scatter_rows += int(ids.size)
            rows = np.asarray(rows)
            fill = self._fills[name]
            if rows.shape != (ids.size,) + fill.shape:
                raise ValueError(
                    f"scatter of field {name!r}: rows shape {rows.shape} "
                    f"!= {(ids.size,) + fill.shape}"
                )
            if rows.dtype != fill.dtype:
                raise ValueError(
                    f"scatter of field {name!r}: dtype {rows.dtype} != "
                    f"registered {fill.dtype} (an implicit cast here would "
                    "silently change restored state)"
                )
            for cid, pos, local in self._by_chunk(ids):
                chunk = self._chunks.get(cid)
                if chunk is None:
                    chunk = self._materialize(cid)
                else:
                    self._touch(cid)
                if name not in chunk:
                    chunk[name] = np.broadcast_to(
                        fill, (self._chunk_rows(cid),) + fill.shape
                    ).copy()
                chunk[name][local] = rows[pos]
                self._dirty.add(cid)
            self._ensure_budget()

    def _read_chunk(self, cid: int) -> Dict[str, np.ndarray]:
        """Read-only array views of chunk `cid`'s current on-disk
        version, through the per-file cache (versions are immutable):
        one zip parse serves every field of a gather batch.
        `spill_reads` counts the cache MISSES — actual file opens.
        A read that fails verification past the retry walks the repair
        ladder (`_repair_chunk`), which may re-point `_files[cid]` at a
        prior version or delete the entry entirely (pristine re-init —
        the returned dict is then empty and every field falls back to
        its fill row)."""
        fname = self._files[cid]
        arrs = self._mmap_cache.get(fname)
        if arrs is not None:
            return arrs
        self.spill_reads += 1
        try:
            arrs = self._load_verified(fname)
        except (OSError, IntegrityError) as e:
            return self._repair_chunk(cid, fname, e)
        self._cache_views(fname, arrs)
        return arrs

    def _cache_views(self, fname: str, arrs: Dict[str, np.ndarray]) -> None:
        self._mmap_cache[fname] = arrs
        while len(self._mmap_cache) > self._mmap_cache_max:
            self._mmap_cache.pop(next(iter(self._mmap_cache)))

    def _load_verified(self, fname: str) -> Dict[str, np.ndarray]:
        """One chunk file -> array views, checksum-verified BEFORE any
        row can reach a gather, with bounded retry (transient injected
        faults — and real flaky disks — heal on a clean re-read, which
        `retry_heals` counts). Raises OSError/IntegrityError when every
        attempt fails; the caller decides repair vs refusal."""
        path = self._chunk_path(fname)
        digest = self._digests.get(fname) if self.checksums else None
        if self._io is None and digest is None:
            # fast path: no chaos shim, nothing to verify (checksums
            # off, or a legacy/unmanifested version) — the pre-integrity
            # zero-copy mmap read, bit for bit
            return _mmap_npz(path)
        fails = [0]

        def attempt() -> Dict[str, np.ndarray]:
            try:
                if self._io is not None:
                    data = self._io.read_bytes(path)
                    if not verify_digest(data, digest):
                        raise IntegrityError(
                            f"client-store chunk {fname} failed checksum "
                            f"verification at {path}",
                            path=path,
                        )
                    if digest is not None:
                        self.verified_reads += 1
                    return _npz_from_bytes(data, path)
                # no shim: verify over a throwaway mapping (page-cache
                # warm for the view parse that follows)
                with open(path, "rb") as f:
                    mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                    try:
                        ok = verify_digest(mm, digest)
                    finally:
                        mm.close()
                if not ok:
                    raise IntegrityError(
                        f"client-store chunk {fname} failed checksum "
                        f"verification at {path}",
                        path=path,
                    )
                self.verified_reads += 1
                return _mmap_npz(path)
            except (OSError, IntegrityError) as e:
                fails[0] += 1
                if isinstance(e, IntegrityError):
                    self.integrity_failures += 1
                raise

        out = retry_io(
            attempt,
            what=f"client-store chunk read ({fname})",
            attempts=self.io_retries,
            retry_on=(OSError, IntegrityError),
        )
        if fails[0]:
            self.retry_heals += 1
        return out

    def _retained_digests(self, root: str) -> Dict[str, dict]:
        """Chunk digests every retained manifest records (the repair
        ladder verifies PRIOR versions against the manifest that
        committed them, not just the live map's digests)."""
        out: Dict[str, dict] = {}
        try:
            entries = sorted(os.listdir(root))
        except OSError:
            return out
        for entry in entries:
            if not (
                entry.startswith("manifest_step_")
                and entry.endswith(".json")
            ):
                continue
            try:
                with open(os.path.join(root, entry)) as f:
                    out.update(json.load(f).get("digests", {}))
            except (OSError, ValueError):
                continue
        return out

    def _repair_chunk(
        self, cid: int, fname: str, err: Exception
    ) -> Dict[str, np.ndarray]:
        """The repair ladder for a chunk whose current version failed
        past the retry (module docstring): newest intact prior version;
        else pristine re-init by construction, counted; else — repair
        disabled — refuse loudly naming the chunk."""
        root = self._root(self._save_dir)
        if not self.repair:
            raise IntegrityError(
                f"client-store chunk {fname} is corrupt and repair is "
                f"disabled: {err}",
                path=self._chunk_path(fname),
            )
        for f, d in self._retained_digests(root).items():
            self._digests.setdefault(f, d)
        prefix = f"chunk_{cid:06d}_v"
        try:
            priors = sorted(
                (
                    e
                    for e in os.listdir(root)
                    if e.startswith(prefix)
                    and e.endswith(".npz")
                    and e != fname
                ),
                reverse=True,  # newest version first
            )
        except OSError:
            priors = []
        for prior in priors:
            try:
                arrs = self._load_verified(prior)
            except (OSError, IntegrityError):
                continue
            self._files[cid] = prior
            self.repairs_prior += 1
            self._count_repairs(cid)
            self._cache_views(prior, arrs)
            warnings.warn(
                f"client-store chunk {cid} repaired: adopted prior "
                f"intact version {prior} (current {fname} failed: {err})"
            )
            return arrs
        # no intact version anywhere: the chunk reverts to pristine —
        # correct BY CONSTRUCTION (every field falls back to its
        # registered fill row, the same state a never-touched chunk
        # holds) — and the loss is counted, per-client, for the
        # telemetry penalties
        del self._files[cid]
        self._dirty.discard(cid)
        self.repairs_reinit += 1
        self._count_repairs(cid)
        warnings.warn(
            f"client-store chunk {cid} has no intact version "
            f"(current {fname} failed: {err}); re-initialized pristine"
        )
        return {}

    def _count_repairs(self, cid: int) -> None:
        lo = cid * self.chunk_clients
        for vid in range(lo, lo + self._chunk_rows(cid)):
            self._repaired[vid] = self._repaired.get(vid, 0) + 1

    def take_repaired(self) -> Dict[int, int]:
        """Drain the per-client repair counts accumulated since the
        last call (`{vid: repairs}`) — the trainer folds them into the
        `telem/repairs` reliability field each loop."""
        with self._lock:
            out = self._repaired
            self._repaired = {}
            return out

    def verify_all(self) -> dict:
        """Verify every manifest-referenced chunk file's checksum —
        no adoption, no repair: the resume-time gate (and scrub's
        report pass). Raises IntegrityError naming the first chunk that
        fails past the retry; legacy files without a digest are skipped
        (read-only accepted by the format contract). Returns
        `{"verified": n, "chunks": total}`."""
        with self._lock:
            checked = 0
            for cid in sorted(self._files):
                fname = self._files[cid]
                digest = (
                    self._digests.get(fname) if self.checksums else None
                )
                if digest is None:
                    continue
                path = self._chunk_path(fname)

                def attempt(path=path, fname=fname, digest=digest):
                    if self._io is not None:
                        data = self._io.read_bytes(path)
                    else:
                        with open(path, "rb") as f:
                            data = f.read()
                    if not verify_digest(data, digest):
                        self.integrity_failures += 1
                        raise IntegrityError(
                            f"client-store chunk {fname} failed checksum "
                            f"verification at {path}",
                            path=path,
                        )

                retry_io(
                    attempt,
                    what=f"client-store chunk verify ({fname})",
                    attempts=self.io_retries,
                    retry_on=(OSError, IntegrityError),
                )
                self.verified_reads += 1
                checked += 1
            return {"verified": checked, "chunks": len(self._files)}

    def integrity_digest(self) -> dict:
        """The small integrity digest the trainer logs as the
        `integrity` record and stamps into the status sidecar
        (docs/OBSERVABILITY.md): checksum config + the
        detect/heal/repair counters."""
        with self._lock:
            return {
                "checksums": self.checksums,
                "alg": CHECKSUM_ALG,
                "verified_reads": int(self.verified_reads),
                "failures": int(self.integrity_failures),
                "retry_heals": int(self.retry_heals),
                "repairs_prior": int(self.repairs_prior),
                "repairs_reinit": int(self.repairs_reinit),
            }

    def _materialize(self, cid: int) -> Dict[str, np.ndarray]:
        """Bring chunk `cid` into the resident set for writing: a full
        (writable) copy of its on-disk version when one exists, else an
        empty dict whose fields fill lazily."""
        if cid in self._files:
            chunk = {
                k: np.array(v)  # writable copies off the shared views
                for k, v in self._read_chunk(cid).items()
            }
        else:
            chunk = {}
        self._chunks[cid] = chunk
        return chunk

    def touched_chunks(self, ids: np.ndarray) -> set:
        """Chunk ids a scatter of `ids` dirties (the O(C) bound of one
        loop's checkpoint delta: ≤ len(ids) chunks + the manifest)."""
        return {self._chunk_of(v) for v in self._check_ids(ids)}

    # --------------------------------------------------------- residency

    @contextlib.contextmanager
    def batched_writes(self):
        """Defer residency enforcement to the end of a multi-field
        write batch (the trainer's cohort scatter: one scatter call per
        field over the same chunks). Without this, each field's scatter
        would spill the over-budget chunks and the next field's would
        reload them — full chunk I/O multiplied by the field count.
        Inside the batch the resident set may exceed the budget by the
        cohort's chunks, the same O(resident + cohort) transient the
        per-call rule allows. No-op without a budget."""
        with self._lock:
            self._defer_budget = True
        try:
            yield
        finally:
            with self._lock:
                self._defer_budget = False
                self._ensure_budget()

    def _ensure_budget(self) -> None:
        """Evict LRU chunks until the resident set fits the budget.

        Clean chunks (current version on disk) drop for free; dirty
        ones spill — written as the next version through the same
        tmp+fsync+rename path `save` uses, so the following manifest
        just references the file. Invariant: every clean materialized
        chunk HAS a file (chunks materialize dirty and only become
        clean via save/spill, or arrive clean from a load), so eviction
        never loses the only copy. A spill deletes the version it
        supersedes when NO retained manifest references it
        (`_protected`) — otherwise a long run without checkpoints
        would accumulate one full dead chunk file per eviction, and
        only `save`'s GC (which such a run never reaches) could
        reclaim them.
        """
        if self.resident_chunks is None or self._defer_budget:
            return
        while len(self._chunks) > self.resident_chunks:
            cid = next(iter(self._chunks))  # LRU head
            if cid in self._dirty or cid not in self._files:
                old = self._files.get(cid)
                self.spill_bytes += self._write_chunk(cid, self._spill_dir)
                self._dirty.discard(cid)
                if old is not None and old not in self._protected:
                    self._mmap_cache.pop(old, None)
                    try:
                        os.remove(self._chunk_path(old))
                    except OSError:
                        pass  # best-effort, like save's GC
            del self._chunks[cid]
            self.evictions += 1

    def _root(self, directory: str) -> str:
        return os.path.abspath(os.path.join(directory, "client_store"))

    def _chunk_path(self, fname: str) -> str:
        # chunk files live under the spill/save root; the two are
        # asserted identical in save()
        return os.path.join(self._root(self._save_dir), fname)

    # the directory chunk files are read back from: the spill dir until
    # a save/load names one (they must agree — see save)
    @property
    def _save_dir(self) -> str:
        if self._dir is not None:
            return self._dir
        if self._spill_dir is not None:
            return self._spill_dir
        raise RuntimeError(
            "no chunk directory known yet (no save/load happened and no "
            "spill_dir was configured)"
        )

    _dir: Optional[str] = None

    def _write_chunk(self, cid: int, directory: str) -> int:
        """One chunk -> its next versioned `.npz` (tmp+fsync+rename);
        updates `_files` and returns the bytes written. THE one chunk
        writer — `save` and the dirty-spill eviction share it, so the
        on-disk format and the GC's filename rules cannot drift. The
        payload is serialized once up front so its digest covers
        exactly the bytes that land, and transient write faults
        (injected ioerror/enospc, real flaky disks) are absorbed by the
        bounded retry — the chaos shim refuses BEFORE any bytes move,
        so a retried write never half-lands."""
        root = self._root(directory)
        os.makedirs(root, exist_ok=True)
        self._seq += 1
        fname = f"chunk_{cid:06d}_v{self._seq:08d}.npz"
        tmp = os.path.join(root, f".tmp_{fname}")
        buf = _io.BytesIO()
        np.savez(buf, **self._chunks[cid])
        payload = buf.getvalue()

        def write():
            if self._io is not None:
                self._io.before_write(f"client-store chunk {fname}")
            with open(tmp, "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())

        retry_io(
            write,
            what=f"client-store chunk write ({fname})",
            attempts=self.io_retries,
        )
        os.replace(tmp, os.path.join(root, fname))
        if self.checksums:
            self._digests[fname] = checksum(payload)
        self._files[cid] = fname
        return len(payload)

    # --------------------------------------------------------- checkpointing

    # manifests retained per save: the newest one plus enough history to
    # cover the crash window between a store save and its checkpoint's
    # orbax commit (resume then falls back exactly one step). Retaining
    # N manifests bounds disk at O(population touched) + N*O(C) chunk
    # versions; without pruning, every superseded chunk version would
    # stay referenced by some historical manifest forever.
    keep_manifests: int = 2

    def save(self, directory: str, step: int) -> str:
        """Write the dirty chunks + the step manifest; return its path.

        Called by `Trainer.save` BEFORE the orbax checkpoint of the same
        step is committed: a crash between the two leaves this manifest
        dangling (no checkpoint names it), and resume falls back to the
        previous checkpoint + its manifest — both still intact, because
        chunk files are versioned (`chunk_<cid>_v<seq>.npz`), never
        overwritten in place. After the manifest commit, manifests older
        than the newest `keep_manifests` are pruned and chunk files no
        retained manifest references (superseded versions, crashed-save
        orphans, eviction spills the crashed run never committed, stale
        `.tmp_` staging files) are garbage-collected — resume therefore
        reaches the newest `keep_manifests` snapshots; falling back
        further (multiple consecutive torn checkpoints) fails loudly in
        `load` rather than restoring silently-wrong rows. Under a
        residency budget the now-all-clean resident set is shed back to
        the budget before returning.
        """
        with self._lock:
            if self._spill_dir is not None and os.path.abspath(
                directory
            ) != self._spill_dir:
                raise ValueError(
                    f"save directory {directory!r} != configured spill "
                    f"dir {self._spill_dir!r}: eviction-spilled chunk "
                    "versions would be invisible to this manifest"
                )
            self._dir = os.path.abspath(directory)
            root = self._root(directory)
            os.makedirs(root, exist_ok=True)
            for cid in sorted(self._dirty):
                self._write_chunk(cid, directory)
            self._dirty.clear()
            manifest = {
                "version": _MANIFEST_VERSION,
                "step": int(step),
                "n_virtual": self.n_virtual,
                "chunk_clients": self.chunk_clients,
                "seq": self._seq,
                "chunks": {
                    str(c): f for c, f in sorted(self._files.items())
                },
                "fields": {
                    name: {
                        "shape": list(row.shape),
                        "dtype": str(row.dtype),
                    }
                    for name, row in sorted(self._fills.items())
                },
                # per-chunk-file digests, verified on every read before
                # a row can reach a gather (module docstring); a file
                # without one (checksums off when it was written) stays
                # read-only accepted like a v1 legacy chunk
                "digests": {
                    f: self._digests[f]
                    for f in sorted(set(self._files.values()))
                    if f in self._digests
                },
            }
            # the manifest carries its own CRC (fault/io.py stamp_crc):
            # a bit-rotted-but-parsable manifest must not restore —
            # it indexes every chunk of the snapshot
            text = stamp_crc(manifest)
            path = _manifest_path(root, step)
            tmp = path + ".tmp"

            def write_manifest():
                if self._io is not None:
                    self._io.before_write(
                        f"client-store manifest step {step}"
                    )
                with open(tmp, "w") as f:
                    f.write(text)
                    f.flush()
                    os.fsync(f.fileno())

            retry_io(
                write_manifest,
                what=f"client-store manifest write (step {step})",
                attempts=self.io_retries,
            )
            os.replace(tmp, path)
            self._gc(root)
            self._ensure_budget()
            return path

    def _gc(self, root: str) -> None:
        """Prune old manifests, then delete unreferenced files.

        Best-effort: any OS error leaves files behind for the next save
        to reclaim, never fails the checkpoint. A torn (unparseable)
        retained manifest aborts chunk GC entirely — its references are
        unknowable, and deleting a chunk it might name would turn a
        recoverable situation into data loss. Files named by the LIVE
        `_files` map are always kept: an eviction-spilled version
        written since the manifest above is the only copy of a clean
        evicted chunk's current state.
        """
        def is_manifest(entry: str) -> bool:
            # committed manifests only: a crashed writer's staging file
            # (`manifest_step_N.json.tmp`) is never authoritative — it
            # is deleted below, not parsed, so it can't wedge GC forever
            return entry.startswith("manifest_step_") and entry.endswith(
                ".json"
            )

        steps = []
        for entry in os.listdir(root):
            if is_manifest(entry):
                try:
                    steps.append(int(entry[len("manifest_step_"):-5]))
                except ValueError:
                    continue
        for s in sorted(steps)[: -self.keep_manifests]:
            try:
                os.remove(_manifest_path(root, s))
            except OSError:
                pass
        manifest_refs = set()
        for entry in os.listdir(root):
            if not is_manifest(entry):
                continue
            try:
                with open(os.path.join(root, entry)) as f:
                    manifest_refs.update(
                        json.load(f).get("chunks", {}).values()
                    )
            except (OSError, ValueError):
                # torn retained manifest: references unknowable — keep
                # everything (spills must then protect the live map too)
                self._protected |= set(self._files.values())
                return
        # what eviction spills must never delete: every retained
        # manifest's versions (resume reaches any of those snapshots)
        self._protected = set(manifest_refs)
        referenced = manifest_refs | set(self._files.values())
        self._digests = {
            f: d for f, d in self._digests.items() if f in referenced
        }
        for entry in os.listdir(root):
            stale = entry.startswith("chunk_") and entry not in referenced
            if stale or entry.startswith(".tmp_") or entry.endswith(
                ".json.tmp"
            ):
                try:
                    os.remove(os.path.join(root, entry))
                except OSError:
                    pass

    def load(self, directory: str, step: int) -> None:
        """Restore the snapshot `save(directory, step)` committed.

        Chunks named by the manifest become addressable (their files are
        stat-checked now so a half-deleted store fails at restore, not
        mid-run) but are NOT read into RAM: gathers serve rows off the
        memory-mapped files and scatters materialize on demand — a
        restored million-client store costs no more resident memory
        than a fresh one. Everything the manifest doesn't name reverts
        to pristine. Field fills are NOT restored from disk — the caller
        re-registers them from the same deterministic init it built them
        with (common-seed model init), and the manifest's recorded
        shapes/dtypes are cross-checked against that registration so a
        config drift (different model, different rho shape) fails loudly
        instead of broadcasting the wrong fill under restored chunks.
        """
        with self._lock:
            if self._spill_dir is not None and os.path.abspath(
                directory
            ) != self._spill_dir:
                raise ValueError(
                    f"load directory {directory!r} != configured spill "
                    f"dir {self._spill_dir!r}"
                )
            root = self._root(directory)
            path = _manifest_path(root, step)
            if not os.path.exists(path):
                raise FileNotFoundError(
                    f"no client-store manifest for step {step} under "
                    f"{root} (the checkpoint was written without cohort "
                    "mode, or the store snapshot was deleted)"
                )
            with open(path) as f:
                manifest = json.load(f)
            version = manifest.get("version")
            if version not in (1, _MANIFEST_VERSION):
                raise ValueError(
                    f"client-store manifest version "
                    f"{version} != supported "
                    f"{_MANIFEST_VERSION}"
                )
            if version >= 2 and not verify_crc(manifest):
                # a v2 manifest ALWAYS carries a self-CRC; a parsable
                # document that fails it is bit rot, and it indexes the
                # whole snapshot — refuse so the trainer's restore loop
                # falls back to the previous intact checkpoint
                raise IntegrityError(
                    f"client-store manifest for step {step} failed its "
                    f"self-checksum at {path}",
                    path=path,
                )
            for key, mine in (
                ("n_virtual", self.n_virtual),
                ("chunk_clients", self.chunk_clients),
            ):
                if int(manifest[key]) != mine:
                    raise ValueError(
                        f"client-store manifest {key}={manifest[key]} but "
                        f"this run configured {mine}: the snapshot indexes "
                        "a different virtual population and cannot be "
                        "restored onto it"
                    )
            for name, meta in manifest.get("fields", {}).items():
                if name in self._fills:
                    row = self._fills[name]
                    if (
                        list(row.shape) != list(meta["shape"])
                        or str(row.dtype) != meta["dtype"]
                    ):
                        raise ValueError(
                            f"client-store field {name!r} was saved with "
                            f"shape {meta['shape']} dtype {meta['dtype']} "
                            f"but this run registered shape "
                            f"{list(row.shape)} dtype {row.dtype}"
                        )
            files = {
                int(c): fname for c, fname in manifest["chunks"].items()
            }
            missing = [
                f
                for f in files.values()
                if not os.path.exists(os.path.join(root, f))
            ]
            if missing:
                raise FileNotFoundError(
                    f"client-store manifest step {step} names chunk "
                    f"file(s) that do not exist under {root}: "
                    f"{sorted(missing)[:4]}"
                )
            self._dir = os.path.abspath(directory)
            self._chunks.clear()
            self._dirty.clear()
            self._mmap_cache.clear()
            self._files = files
            # v1 manifests carry no digests: their chunks restore
            # read-only accepted/unverified until the next save rewrites
            # them under v2 (the legacy-migration path, docs/SCALE.md)
            self._digests = dict(manifest.get("digests", {}))
            # conservative: this manifest's versions are committed (and
            # a sibling retained manifest may reference more — the next
            # save's GC scan refines the set); spills must not delete
            # any of them
            self._protected |= set(files.values())
            self._seq = int(manifest.get("seq", 0))
            self._saved_fields = dict(manifest.get("fields", {}))

    # ------------------------------------------------------------- summary

    def materialized_chunks(self) -> int:
        return len(self._chunks)

    def residency(self) -> dict:
        """The small live digest the trainer folds into each round's
        `memory` record and the `watch` status sidecar (docs/SCALE.md
        §Spilled store): resident/on-disk chunk counts, the budget, and
        the eviction/spill counters."""
        with self._lock:
            return {
                "resident_chunks": len(self._chunks),
                "resident_budget": self.resident_chunks,
                "on_disk_chunks": len(self._files),
                "evictions": int(self.evictions),
                "spill_bytes": int(self.spill_bytes),
                "spill_reads": int(self.spill_reads),
            }

    def traffic(self) -> dict:
        """Cumulative host-side row traffic: how many rows every gather
        and scatter has moved since construction. Process-local (like
        the storage-fault counter, a resumed run restarts from zero);
        the chaos oracle reads it off the status sidecar to assert the
        cohort data path actually moved rows in cohort mode."""
        with self._lock:
            return {
                "gather_calls": int(self.gather_calls),
                "gather_rows": int(self.gather_rows),
                "scatter_calls": int(self.scatter_calls),
                "scatter_rows": int(self.scatter_rows),
            }

    def summary(self) -> dict:
        """Small host-memory/occupancy digest for the end-of-run log."""
        with self._lock:
            rows = sum(
                next(iter(c.values())).shape[0] if c else 0
                for c in self._chunks.values()
            )
            nbytes = sum(
                a.nbytes for c in self._chunks.values() for a in c.values()
            )
            return {
                "n_virtual": self.n_virtual,
                "chunk_clients": self.chunk_clients,
                "chunks_total": -(-self.n_virtual // self.chunk_clients),
                "chunks_materialized": len(self._chunks),
                "rows_materialized": int(rows),
                "host_bytes": int(nbytes),
                "fields": list(self.fields),
                **self.residency(),
            }
