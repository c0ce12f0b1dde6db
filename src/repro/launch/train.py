"""Production training launcher: mesh + sharded train step + checkpointed
loop. On the CPU container it runs real (small) configs on the host mesh;
on a TPU fleet the same entrypoint spans pods (jax.distributed initializes
from the cluster env; the mesh/profile flags pick the parallelism layout).

    PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
        --smoke --steps 50 --profile tp_fsdp

Distributed-optimization flags map to §Perf levers:
  --profile tp_fsdp|tp_only|replicated   weight sharding layout
  --microbatch N                         gradient accumulation
  --grad-compress                        int8 DP all-reduce + error feedback
  --remat / --no-remat                   activation checkpointing
XLA latency-hiding scheduler flags (compute/comm overlap) are applied via
REPRO_XLA_FLAGS_EXTRA before jax init.
"""
import os

_EXTRA = os.environ.get("REPRO_XLA_FLAGS_EXTRA")
if _EXTRA:
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + _EXTRA

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import apply_method, get_arch
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.distributed.sharding import batch_specs, tree_param_specs
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim import AdamWConfig, linear_warmup_linear_decay
from repro.train.step import TrainTask, init_train_state, make_train_step


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--method", default="clipped_softmax")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--profile", default="tp_fsdp")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--mesh", choices=["host", "single", "multi"],
                    default="host")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()

    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices")

    spec = get_arch(args.arch)
    cfg = spec.smoke() if args.smoke else spec.full()
    cfg = apply_method(cfg, args.method)
    cfg = dataclasses.replace(cfg, remat=not args.no_remat,
                              max_seq_len=max(cfg.max_seq_len, args.seq_len),
                              train_seq_len=args.seq_len)
    loss_kind = "clm" if cfg.causal else "frames"

    task = TrainTask(
        cfg=cfg, loss_kind=loss_kind,
        optimizer=AdamWConfig(lr=args.lr),
        schedule=linear_warmup_linear_decay(args.steps // 10, args.steps),
        microbatch=args.microbatch, grad_compress=args.grad_compress)

    with jax.sharding.set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), task)
        state_specs = tree_param_specs(state, args.profile, mesh)
        state = jax.device_put(state, _ns(mesh, state_specs))
        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            state, start = restore_checkpoint(args.ckpt_dir, state)
            print(f"[resume] step {start}")

        data = SyntheticLMConfig(vocab_size=cfg.vocab_size,
                                 seq_len=args.seq_len,
                                 batch_size=args.batch_size)
        pipe = SyntheticLM(data)
        bspecs = None
        step_fn = jax.jit(make_train_step(task),
                          in_shardings=(_ns(mesh, state_specs), None),
                          out_shardings=(_ns(mesh, state_specs), None),
                          donate_argnums=(0,))

        import time
        durs = []
        for step in range(start, args.steps):
            batch = jax.tree_util.tree_map(jnp.asarray,
                                           pipe.batch(step, loss_kind))
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            metrics["loss"].block_until_ready()
            durs.append(time.perf_counter() - t0)
            if (step + 1) % max(args.steps // 10, 1) == 0:
                print(f"step {step+1:6d} loss {float(metrics['loss']):.4f} "
                      f"{durs[-1]*1e3:.0f}ms")
            if args.ckpt_every and args.ckpt_dir and \
                    (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1, state)
        print(f"median step {np.median(durs)*1e3:.0f} ms")


if __name__ == "__main__":
    main()
