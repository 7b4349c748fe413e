"""Multi-device SPMD training over a jax.sharding.Mesh, with checkpointing.

Shards entities over every visible device, trains with the all_gather
exchange, checkpoints each iteration, then resumes from the checkpoint to
show crash recovery. Run on real chips as-is, or simulate an 8-device mesh
on CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/sharded_training.py

(``--cpu`` does the platform half of that in-process.)

Multi-host (one process per host over DCN) uses the same code path after
``cfk_tpu.parallel.mesh.initialize_distributed()`` +
``make_multihost_mesh()``; see ARCHITECTURE.md §SPMD.
"""

import sys
import tempfile

if "--cpu" in sys.argv:
    import jax

    jax.config.update("jax_platforms", "cpu")

import jax

from cfk_tpu import ALSConfig, parse_netflix
from cfk_tpu.data.blocks import Dataset
from cfk_tpu.eval.metrics import mse_rmse_from_blocks
from cfk_tpu.parallel.mesh import make_mesh
from cfk_tpu.parallel.spmd import train_als_sharded
from cfk_tpu.transport.checkpoint import CheckpointManager


def main() -> None:
    n = len(jax.devices())
    path = "/root/reference/data/data_sample_tiny.txt"
    dataset = Dataset.from_coo(parse_netflix(path), num_shards=n)
    config = ALSConfig(rank=5, lam=0.05, num_iterations=7, seed=0, num_shards=n)
    mesh = make_mesh(n)

    ckdir = tempfile.mkdtemp(prefix="cfk-ck-")
    model = train_als_sharded(
        dataset, config, mesh, checkpoint_manager=CheckpointManager(ckdir)
    )
    mse, rmse = mse_rmse_from_blocks(model.predict_dense(), dataset)
    print(f"{n}-way sharded: MSE={mse:.4f} RMSE={rmse:.4f}")

    # "Crash" and resume: a fresh trainer picks up the final checkpoint and
    # has nothing left to do — factors match the uninterrupted run exactly.
    resumed = train_als_sharded(
        dataset, config, mesh, checkpoint_manager=CheckpointManager(ckdir)
    )
    mse2, rmse2 = mse_rmse_from_blocks(resumed.predict_dense(), dataset)
    assert abs(mse - mse2) < 1e-9
    print(f"resumed from {ckdir}: identical (MSE={mse2:.4f})")


if __name__ == "__main__":
    main()
