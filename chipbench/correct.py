"""The comparisons that decide `correct`, kept with the benchmark.

Each takes plain records and arrays, so the tests can hold them to
hand-built cases; `run.py` feeds them the trainer's recorder series and
host copies of its `[K, N]` parameter matrix.
"""

from __future__ import annotations

import math

import numpy as np


def config_as_filed(expect: dict, found: dict) -> bool:
    """The sizes the configuration file states are the sizes that ran."""
    return all(found.get(k) == v for k, v in expect.items())


def partial_exchange(before, after, segments, equal_across_clients: bool):
    """One round trained and exchanged ONE group: every client's vector
    is bitwise unchanged outside the group's segments, changed inside
    them, and (FedAvg) bitwise equal across clients inside them.

    `before`/`after`: `[K, N]` float32 host arrays; `segments`: objects
    with `.start` and `.size`. Returns `(ok, why)`.
    """
    b = np.ascontiguousarray(before).view(np.uint32)
    a = np.ascontiguousarray(after).view(np.uint32)
    inside = np.zeros(b.shape[-1], bool)
    for s in segments:
        inside[s.start:s.start + s.size] = True
    if not np.array_equal(b[:, ~inside], a[:, ~inside]):
        return False, "parameters outside the active group changed"
    if np.array_equal(b[:, inside], a[:, inside]):
        return False, "the active group did not move"
    if equal_across_clients and not (a[:, inside] == a[:1, inside]).all():
        return False, "clients disagree inside the exchanged group"
    return True, ""


def _loss_values(records: list) -> list:
    return [float(v) for r in records for v in np.ravel(r["value"])]


def losses_sound(warmup: list, window: list) -> bool:
    """Every train loss of warm-up and window is finite, and the last
    window loop's mean is below the first warm-up step's."""
    warm, win = _loss_values(warmup), _loss_values(window)
    if not warm or not win or not all(map(math.isfinite, warm + win)):
        return False
    last_loop = max(r["nloop"] for r in window)
    last = _loss_values([r for r in window if r["nloop"] == last_loop])
    first = _loss_values(warmup[:1])
    return sum(last) / len(last) < sum(first) / len(first)


def expected_comm_bytes(rounds, group_sizes, *, nadmm, n_clients, dtype_bytes) -> int:
    """Uplink bytes of the window by the benchmark's own arithmetic: each
    round makes `nadmm` exchanges in which each of `n_clients` clients
    sends its active group's coordinates."""
    return sum(
        nadmm * n_clients * group_sizes[r["group"]] * dtype_bytes for r in rounds
    )


def comm_bytes_match(records, rounds, group_sizes, **kw) -> bool:
    """The comm ledger's bytes for the window equal the expected sum."""
    return sum(int(r["value"]) for r in records) == expected_comm_bytes(
        rounds, group_sizes, **kw
    )


def failed_rounds(loss_records: list, fault_records: list) -> set:
    """`(nloop, group)` of every round with a non-finite loss or a
    detected fault (a rollback is recorded as one)."""
    bad = {
        (r["nloop"], r["group"])
        for r in loss_records
        if not all(math.isfinite(float(v)) for v in np.ravel(r["value"]))
    }
    return bad | {(r.get("nloop"), r.get("group")) for r in fault_records}
