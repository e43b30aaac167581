import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step for train
shapes; prefill/decode steps for serving shapes), binds in/out shardings
from the arch's logical-axis rules, lowers against ShapeDtypeStruct
inputs (zero allocation), compiles, and records:

* ``memory_analysis()``  — proves the cell fits per-device HBM,
* ``cost_analysis()``    — FLOPs / bytes for the roofline,
* parsed collective bytes from the compiled HLO text.

Results land in ``results/dryrun/<arch>__<shape>__<mesh>.json``; the
roofline table (EXPERIMENTS.md §Roofline) and the perf loop read them.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k [--multi-pod] [--all]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.roofline import model_flops, roofline_from_compiled
from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh, mesh_chips, mesh_label
from repro.models.model import SHAPES, build, input_specs, shape_applicable
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import rules_for
from repro.train import step as step_mod

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _param_shardings(model, mesh, rules):
    from repro.parallel.sharding import enforce_divisibility, tree_shardings
    return enforce_divisibility(
        tree_shardings(model.param_axes(), mesh, rules),
        model.param_shapes())


def _eval_state_specs(model, mesh, rules):
    """ShapeDtypeStructs + shardings for the train state (no allocation)."""
    state_shapes = jax.eval_shape(
        lambda k: step_mod.init_train_state(model, k), jax.random.key(0))
    shardings = step_mod.state_shardings(model, mesh, rules)
    return state_shapes, shardings


DEFAULT_N_MICRO = 4   # grad-accum for train cells: fits 16 GB/chip HBM


def dryrun_cell(arch: str, shape: str, *, multi_pod: bool = False,
                verbose: bool = True, opt_overrides: dict | None = None,
                n_micro: int | None = None):
    """Lower+compile one cell. Returns the result record (dict)."""
    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "status": "skipped",
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    rules = rules_for(cfg, mesh, mode="train" if shape.startswith("train")
                      else "serve")
    model = build(cfg)
    seq, gbatch, kind = SHAPES[shape]
    specs = input_specs(cfg, shape)
    batch_sh = step_mod.batch_shardings(cfg, shape, mesh, rules)

    t0 = time.monotonic()
    if kind == "train":
        opt_cfg = AdamWConfig(**(opt_overrides or {}))
        nm = DEFAULT_N_MICRO if n_micro is None else n_micro
        fn = step_mod.make_train_step(model, opt_cfg, mesh=mesh,
                                      rules=rules, n_micro=nm)
        state_shapes, state_sh = _eval_state_specs(model, mesh, rules)
        jitted = jax.jit(fn,
                         in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None))
        lowered = jitted.lower(state_shapes, specs)
        tokens = gbatch * seq
    elif kind == "prefill":
        fn = step_mod.make_prefill_step(model, mesh=mesh, rules=rules)
        param_sh = _param_shardings(model, mesh, rules)
        param_shapes = model.param_shapes(jnp.bfloat16)   # serving weights
        cache_sh = step_mod.cache_shardings(
            model, gbatch, step_mod.prefill_cache_len(seq), mesh, rules)
        jitted = jax.jit(fn, in_shardings=(param_sh, batch_sh),
                         out_shardings=(None, cache_sh))
        lowered = jitted.lower(param_shapes, specs)
        tokens = gbatch * seq
    else:  # decode
        fn = step_mod.make_decode_step(model, mesh=mesh, rules=rules)
        param_sh = _param_shardings(model, mesh, rules)
        param_shapes = model.param_shapes(jnp.bfloat16)   # serving weights
        cache_shapes = model.cache_specs(gbatch, seq)
        cache_sh = step_mod.cache_shardings(model, gbatch, seq, mesh, rules)
        tok_spec = jax.ShapeDtypeStruct((gbatch, 1), jnp.int32)
        pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(fn, in_shardings=(param_sh, cache_sh,
                                           batch_sh["tokens"], None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))   # in-place cache updates
        lowered = jitted.lower(param_shapes, cache_shapes, tok_spec,
                               pos_spec)
        tokens = gbatch  # one new token per sequence

    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    mem_rec = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                        None),
    }
    mflops = model_flops(cfg, model.n_params(), model.n_active_params(),
                         tokens, kind)
    hlo_text = compiled.as_text()
    rl = roofline_from_compiled(
        compiled, arch=arch, shape=shape, mesh=mesh_label(mesh),
        chips=chips, model_flops=mflops, hlo_text=hlo_text)

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_label(mesh),
        "chips": chips, "kind": kind, "status": "ok",
        "compile_s": t_compile,
        "memory": mem_rec,
        "hlo_flops": rl.hlo_flops,
        "hlo_bytes": rl.hlo_bytes,
        "collective_bytes": rl.collective_bytes,
        "collectives": rl.collectives,
        "model_flops": mflops,
        "compute_s": rl.compute_s,
        "memory_s": rl.memory_s,
        "collective_s": rl.collective_s,
        "dominant": rl.dominant,
        "useful_ratio": rl.useful_flops_ratio,
        "roofline_frac": rl.roofline_fraction,
    }
    if verbose:
        print(f"[{arch} × {shape} × {mesh_label(mesh)}] compile "
              f"{t_compile:.1f}s | mem {mem_rec} | "
              f"compute {rl.compute_s*1e3:.2f}ms memory "
              f"{rl.memory_s*1e3:.2f}ms collective "
              f"{rl.collective_s*1e3:.2f}ms -> {rl.dominant}-bound, "
              f"useful {rl.useful_flops_ratio:.2f}, "
              f"roofline {rl.roofline_fraction:.2%}")
    return rec


def save_record(rec: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec.get('mesh', 'na')}.json"
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) cell")
    args = ap.parse_args()
    from repro import flags
    flags.use_compile_cache()

    cells = []
    archs = [a for a in list_archs() if a != "whisper-tiny-en"] \
        if args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    failures = []
    for arch, shape in cells:
        try:
            rec = dryrun_cell(arch, shape, multi_pod=args.multi_pod,
                              n_micro=args.n_micro)
            rec["multi_pod"] = args.multi_pod
            save_record(rec)
            if rec["status"] == "skipped":
                print(f"[{arch} × {shape}] SKIP: {rec['reason']}")
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells)} cells passed "
          f"({'multi-pod 2x16x16' if args.multi_pod else 'single-pod 16x16'})")


if __name__ == "__main__":
    main()
