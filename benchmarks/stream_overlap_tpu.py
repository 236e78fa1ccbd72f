"""Streamed vs resident data-path epoch on the real chip.

The streaming path (config `hbm_data_budget_mb`; trainer
`_run_stream_epoch`) exists for datasets that do not fit HBM: per-client
native PrefetchBatchers assemble lockstep minibatch chunks host-side and
each chunk's `device_put` is issued while the previous chunk's jitted
scan still runs. This benchmark quantifies the overlap on the flagship
workload: it times (a) the resident path, (b) the streamed path, and
(c) the streamed path's H2D + host-assembly cost alone — if
(b) < (a) + (c), transfer and compute demonstrably overlapped.

Writes stream_overlap_tpu.json. Run: python benchmarks/stream_overlap_tpu.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K, BATCH, STEPS = 3, 32, 24
CHUNK = 6


def main():
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    assert jax.default_backend() == "tpu", jax.default_backend()
    src = synthetic_cifar(n_train=K * BATCH * STEPS, n_test=64)

    def build(stream: bool):
        cfg = get_preset(
            "fedavg_resnet", n_clients=K, batch=BATCH, check_results=False,
            hbm_data_budget_mb=0 if stream else None,
            stream_chunk_steps=CHUNK,
        )
        return Trainer(cfg, verbose=False, source=src)

    def timed_epochs(tr, reps=3):
        gid = tr.group_order[0]
        epoch_fn, _, init_fn = tr._fns(gid)
        lstate, y, z, rho, extra = init_fn(tr.flat)
        times = []
        for _ in range(reps + 1):  # first rep is compile/warmup
            t0 = time.perf_counter()
            if tr._stream:
                lstate, _, _ = tr._run_stream_epoch(epoch_fn, lstate, y, z, rho)
                # _run_stream_epoch fetches losses: already synchronized
            else:
                idx = tr._epoch_indices(0, gid, 0, 0)[:STEPS]
                tr.flat, lstate, tr.stats, losses = epoch_fn(
                    tr.flat, lstate, tr.stats, tr.shard_imgs,
                    tr.shard_labels, idx, tr.mean, tr.std, y, z, rho,
                )
                float(jnp.sum(tr.flat[:, 0]))  # completion barrier
            times.append(time.perf_counter() - t0)
        return min(times[1:])

    t_resident = timed_epochs(build(False))
    tr_s = build(True)
    t_streamed = timed_epochs(tr_s)

    # SERIALIZED streaming: same chunks, but each chunk is assembled and
    # staged only AFTER the previous chunk's result is synchronized —
    # what the epoch costs with zero transfer/compute overlap. (A pure
    # "transfer alone" leg is not measured.)
    from jax.sharding import NamedSharding, PartitionSpec
    from federated_pytorch_test_tpu.parallel import CLIENT_AXIS
    import numpy as np

    sh = NamedSharding(tr_s.mesh, PartitionSpec(None, CLIENT_AXIS))
    gid = tr_s.group_order[0]
    epoch_fn, _, init_fn = tr_s._fns(gid)

    def serial_epoch():
        # fresh optimizer state per call: epoch_fn DONATES (flat, lstate,
        # stats), so a state object from a previous call is a dead buffer
        ls, y, z, rho, _ = init_fn(tr_s.flat)
        flat, stats = tr_s.flat, tr_s.stats
        t0 = time.perf_counter()
        for _ in range(STEPS // CHUNK):
            imgs = np.empty((CHUNK, K, BATCH, 32, 32, 3), np.uint8)
            labs = np.empty((CHUNK, K, BATCH), np.int32)
            for s in range(CHUNK):
                for c in range(K):
                    im, lb = next(tr_s._batchers[c])
                    imgs[s, c], labs[s, c] = im, lb
            di = jax.device_put(imgs, sh)
            dl = jax.device_put(labs, sh)
            flat, ls, stats, l = epoch_fn(
                flat, ls, stats, di, dl, tr_s.mean, tr_s.std, y, z, rho
            )
            float(jnp.sum(l))  # synchronize: no overlap with next chunk
        tr_s.flat, tr_s.stats = flat, stats
        return time.perf_counter() - t0

    serial_epoch()  # warm
    t_serial = min(serial_epoch() for _ in range(2))

    out = {
        "workload": f"ResNet18 FedAvg epoch, {STEPS} minibatches x {K} "
                    f"clients x batch {BATCH}, chunk {CHUNK}",
        "device": str(jax.devices()[0]),
        "resident_epoch_s": round(t_resident, 4),
        "streamed_epoch_s": round(t_streamed, 4),
        "streamed_serialized_s": round(t_serial, 4),
        "stream_overhead_vs_resident_s": round(t_streamed - t_resident, 4),
        "overlap_gain_s": round(t_serial - t_streamed, 4),
        "overlap_demonstrated": bool(t_streamed < t_serial),
        "note": "double-buffered streaming beats the serialized variant "
                "by overlap_gain_s: assembly+H2D rode under the compute",
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "stream_overlap_tpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
