"""Distributed-SCE demo on 8 simulated devices.

Shards the model outputs over a ``data`` axis and the item catalog over a
``model`` axis (vocab-parallel), runs both distributed SCE modes, and
checks them against the single-device oracle — the same code path the
512-chip dry-run lowers.

  PYTHONPATH=src python examples/distributed_sce_demo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.distributed_sce import (  # noqa: E402
    sce_loss_sharded,
    sce_loss_sharded_ref,
)
from repro.core.sce import SCEConfig  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

    key = jax.random.PRNGKey(0)
    N, C, d = 1024, 4096, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (N, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (C, d)) * 0.5
    t = jax.random.randint(jax.random.PRNGKey(3), (N,), 0, C)
    cfg = SCEConfig.from_alpha_beta(N // 2, C, bucket_size_y=128)
    print(f"SCE: n_b={cfg.n_buckets} b_x={cfg.bucket_size_x} "
          f"b_y={cfg.bucket_size_y} (per data shard)")

    for mode in ("exact", "union"):
        with jax.set_mesh(mesh):
            loss = jax.jit(
                lambda x, y: sce_loss_sharded(
                    x, y, t, key=key, cfg=cfg, mesh=mesh, mode=mode
                )
            )(x, y)
        ref = sce_loss_sharded_ref(
            x, y, t, key=key, cfg=cfg, dp_size=2, mode=mode, tp_size=4
        )
        np.testing.assert_allclose(loss, ref, rtol=1e-5)
        print(f"mode={mode:5s}: distributed {float(loss):.5f} == "
              f"single-device oracle {float(ref):.5f}  ✓")

    print("\nwhat moved over the wire (per step, per device):")
    print("  exact : 2 all-gathers of (value, global-id) candidate pairs")
    print("          + 1 psum of (n_b, b_x) partial-LSE merges")
    print("  union : 1 psum of (n_b, b_x) partial (max,sumexp) — ~KBs")
    print("  candidate embeddings never leave their shard in either mode")


if __name__ == "__main__":
    main()
