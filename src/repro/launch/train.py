"""End-to-end training launcher.

Builds the mesh (or runs single-device for CPU smokes), binds shardings,
and drives the fault-tolerant TrainLoop over the synthetic pipeline.

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b \
        --reduced --steps 200 --batch 8 --seq 256 --ckpt /tmp/ckpt
    # forced-device distributed smoke:
    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
        --reduced --devices 4 --mesh 2x2 --steps 10
"""

import argparse
import os


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (set before jax init)")
    ap.add_argument("--mesh", default="",
                    help="DxM data×model mesh (requires --devices)")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro import flags
    flags.use_compile_cache()
    import jax
    from repro.checkpoint.store import CheckpointManager
    from repro.configs import get_config, reduced
    from repro.data.synthetic import SyntheticDataset
    from repro.models.model import build
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import rules_for
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train import step as step_mod

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps)

    mesh = rules = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"))
        rules = rules_for(cfg, mesh, mode="train")

    fn = step_mod.make_train_step(model, opt_cfg, mesh=mesh, rules=rules,
                                  n_micro=args.n_micro)
    state = step_mod.init_train_state(model, jax.random.key(args.seed))
    state_sh = None
    put_batch = None
    if mesh is not None:
        state_sh = step_mod.state_shardings(model, mesh, rules)
        state = jax.device_put(state, state_sh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put_batch(b):
            return {k: jax.device_put(v, NamedSharding(
                mesh, P(*( ("data",) + (None,) * (v.ndim - 1) ))))
                for k, v in b.items()}

        step_fn = jax.jit(fn, in_shardings=(state_sh, None),
                          out_shardings=(state_sh, None), donate_argnums=0)
    else:
        step_fn = jax.jit(fn, donate_argnums=0)

    ds = SyntheticDataset(cfg, seq_len=args.seq, global_batch=args.batch,
                          seed=args.seed)
    ckpt_dir = args.ckpt or os.path.join("/tmp", f"ckpt-{args.arch}")
    ckpt = CheckpointManager(ckpt_dir, keep=3)
    loop_cfg = LoopConfig(total_steps=args.steps,
                          save_every=args.save_every,
                          handle_signals=True)

    def on_step(step, loss):
        if step % 10 == 0 or step == 1:
            print(f"step {step:5d}  loss {loss:.4f}", flush=True)

    loop = TrainLoop(step_fn, ds, ckpt, loop_cfg, put_batch=put_batch,
                     on_step=on_step)
    state, result = loop.run(state, state_shardings=state_sh)
    last = f"{result.losses[-1]:.4f}" if result.losses else "n/a (resumed)"
    print(f"done: {result.final_step} steps, final loss "
          f"{last}, stragglers={len(result.straggler_events)}"
          f"{', PREEMPTED' if result.preempted else ''}")
    return result


if __name__ == "__main__":
    main()
