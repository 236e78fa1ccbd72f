"""K=64 clients on a TPU pod: one client per core, DCN-aware mesh.

Run THIS SAME script on every host of the slice/pod (standard JAX
multi-controller SPMD). `initialize_distributed()` must run before any
other JAX call; `multihost_client_mesh` lays the `clients` axis out so a
slice's clients are ICI-adjacent and consensus psums cross DCN once.

Single-host (or the dev box) it degrades gracefully: the mesh shrinks to
the local devices and the same code runs.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from federated_pytorch_test_tpu.parallel import (
    initialize_distributed,
    multihost_client_mesh,
)

proc = initialize_distributed()  # BEFORE any other JAX call

from federated_pytorch_test_tpu.engine import Trainer, get_preset  # noqa: E402


def main():
    cfg = get_preset(os.environ.get("PRESET", "fedavg_scale64"))
    # dev-box dry run: shrink the preset through env overrides WITHOUT
    # changing the recipe (same init -> mesh -> Trainer.run -> save path
    # a pod runs); e.g. K=8 MODEL=net NLOOP=1 MAX_GROUPS=1 smoke-runs the
    # script on a laptop's virtual mesh (tests/test_examples.py)
    env_to_field = {
        "K": ("n_clients", int),
        "MODEL": ("model", str),
        "NLOOP": ("nloop", int),
        "NADMM": ("nadmm", int),
        "BATCH": ("batch", int),
        "NTRAIN": ("synthetic_n_train", int),
        "NTEST": ("synthetic_n_test", int),
        "MAX_GROUPS": ("max_groups", int),
    }
    over = {
        field: cast(os.environ[name])
        for name, (field, cast) in env_to_field.items()
        if name in os.environ
    }
    if over:
        cfg = cfg.replace(**over)
    mesh = multihost_client_mesh(cfg.n_clients)
    trainer = Trainer(cfg, verbose=(proc == 0), mesh=mesh)
    recorder = trainer.run()
    if proc == 0:
        out = os.environ.get("METRICS_OUT", "scale64_metrics.json")
        recorder.save(out)
        print(f"scale64 run complete -> {out}")


if __name__ == "__main__":
    main()
