import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (tests may scale the placeholder device count down via REPRO_DRYRUN_DEVICES
# *before* jax initializes; the production default above is 512.)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) combo
on placeholder host devices, prove memory/sharding coherence, and extract
the roofline terms (EXPERIMENTS.md §Dry-run / §Roofline).

XLA's cost_analysis counts while-loop (scan) bodies ONCE, so raw numbers
undercount scanned layers.  Each combo therefore compiles three modules:
the production scan module (memory analysis + compile proof) and two small
UNROLLED depth variants (1 and 2 layer-units) whose cost delta gives the
true per-layer flops/bytes/collective bytes:

    total = cost(1 unit) + (units_full - 1) * (cost(2 units) - cost(1 unit))

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k --mesh single          # one combo
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out experiments/dryrun                # the full 40 x 2 sweep
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES
from repro.engine.steps import (make_train_step, make_prefill, step_outputs,
                                make_decode_step, train_state_specs)
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh, make_tiny_mesh
from repro.models import spec as pspec
from repro.models import layers as Lmod
from repro.models.layers import Sharder
from repro.models.registry import build_model, decode_window
from repro.optim.optimizers import adamw
from repro.sharding.rules import default_rules, tree_shardings

PROFILES: dict[str, dict] = {
    "baseline": {},
    # FSDP/ZeRO-3: additionally shard every weight's embed dim over "data";
    # GSPMD inserts per-layer all-gathers inside the scan (beyond-paper
    # optimization, EXPERIMENTS.md §Perf).
    "fsdp": {"embed": ("data",)},
    # padheads: mask-padded Q-heads up to the next multiple of the model
    # axis so attention shards by head instead of by head_dim (fixes the
    # 40-head/12-head all-reduce pathology); math-identical (see
    # tests/test_pad_heads.py).  Combines the rule table of baseline.
    "padheads": {},
    "padheads_fsdp": {"embed": ("data",)},
    # dponly: the paper's own regime — pure data parallelism, params
    # replicated, gradient exchange is THE collective (Horovod semantics).
    # The model axis idles; used to compare the paper's world against the
    # TP/FSDP production shardings in §Perf.
    "dponly": {"heads": (), "kv_heads": (), "mlp": (), "vocab": (),
               "experts": (), "ssm_heads": ()},
}


def apply_profile_cfg(cfg, profile: str):
    if profile.startswith("padheads") and cfg.n_heads % 16 != 0:
        import dataclasses as _dc
        return _dc.replace(cfg, pad_heads_to=-(-cfg.n_heads // 16) * 16)
    return cfg


def rules_for(kind: str, profile: str = "baseline"):
    table = dict(PROFILES[profile])
    if kind == "decode":
        # context-parallel cache: shard the cache sequence dim over whatever
        # axes the batch dim leaves free (long_500k: all of them)
        table["cache_seq"] = ("pod", "data", "model")
    return default_rules(table)


def with_depth(cfg, units: int):
    """Same-family config with ``units`` scan iterations."""
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, n_layers=units * cfg.attn_every)
    if cfg.family == "audio":
        return dataclasses.replace(cfg, n_layers=units,
                                   encoder_layers=units)
    return dataclasses.replace(cfg, n_layers=units)


def depth_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def build_jitted(cfg, shape, mesh, rules, *, window, microbatches: int = 1):
    """-> (jitted_fn, abstract_args). Shared by the main and cost passes."""
    model = build_model(cfg)
    sh = Sharder(mesh, rules)
    if shape.kind == "train":
        state_specs = train_state_specs(model, adamw())
        state_sh = tree_shardings(state_specs, mesh, rules)
        batch_specs = model.input_specs(shape)
        batch_sh = tree_shardings(batch_specs, mesh, rules)
        step = make_train_step(model, adamw(), sh, microbatches=microbatches)
        rep = NamedSharding(mesh, P())
        jitted = jax.jit(step,
                         in_shardings=(state_sh, batch_sh, rep),
                         out_shardings=step_outputs(model, state_sh, rep),
                         donate_argnums=(0,))
        args = (pspec.abstract(state_specs), pspec.abstract(batch_specs),
                jax.ShapeDtypeStruct((), jnp.float32))
    elif shape.kind == "prefill":
        serve_specs = pspec.cast(model.param_specs(), jnp.bfloat16)
        params_sh = tree_shardings(serve_specs, mesh, rules)
        batch_specs = model.input_specs(shape)
        batch_sh = tree_shardings(batch_specs, mesh, rules)
        fn = make_prefill(model, sh, window=window)
        logits_spec = rules.spec_for(("batch", "seq", "vocab"),
                                     (shape.global_batch, shape.seq_len,
                                      cfg.vocab_size), mesh)
        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh),
                         out_shardings=NamedSharding(mesh, logits_spec))
        args = (pspec.abstract(serve_specs), pspec.abstract(batch_specs))
    else:  # decode
        serve_specs = pspec.cast(model.param_specs(), jnp.bfloat16)
        params_sh = tree_shardings(serve_specs, mesh, rules)
        cache_specs = model.cache_specs(shape)
        cache_sh = tree_shardings(cache_specs, mesh, rules)
        batch_specs = model.input_specs(shape)
        batch_sh = tree_shardings(batch_specs, mesh, rules)
        fn = make_decode_step(model, sh, window=window)
        logits_spec = rules.spec_for(("batch", "seq", "vocab"),
                                     (shape.global_batch, 1,
                                      cfg.vocab_size), mesh)
        jitted = jax.jit(fn,
                         in_shardings=(params_sh, cache_sh, batch_sh),
                         out_shardings=(NamedSharding(mesh, logits_spec),
                                        cache_sh),
                         donate_argnums=(1,))
        args = (pspec.abstract(serve_specs), pspec.abstract(cache_specs),
                pspec.abstract(batch_specs))
    return jitted, args


def _cost_record(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    colls = analysis.collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": colls}


def corrected_costs(c1: dict, c2: dict, units_full: int) -> dict:
    """Scan-corrected totals from the 1-unit/2-unit unrolled cost records."""
    def tot(key):
        per = max(0.0, c2[key] - c1[key])
        return c1[key] + (units_full - 1) * per

    kinds = set(c1["coll"]) | set(c2["coll"])
    coll = {}
    for k in kinds:
        a, b = c1["coll"].get(k, 0.0), c2["coll"].get(k, 0.0)
        coll[k] = a + (units_full - 1) * max(0.0, b - a)
    return {"flops": tot("flops"), "bytes": tot("bytes"), "coll": coll,
            "per_layer_flops": max(0.0, c2["flops"] - c1["flops"]),
            "per_layer_bytes": max(0.0, c2["bytes"] - c1["bytes"])}


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               tiny: bool = False, profile: str = "baseline",
               save_hlo: str | None = None, skip_costs: bool = False,
               rules=None, microbatches: int = 1) -> dict:
    cfg = apply_profile_cfg(get_config(arch), profile)
    shape = SHAPES[shape_name]
    mesh = (make_tiny_mesh(multi_pod=multi_pod) if tiny
            else make_production_mesh(multi_pod=multi_pod))
    n_dev = mesh.size
    rules = rules or rules_for(shape.kind, profile)
    window = decode_window(cfg, shape.seq_len)

    # ---- main compile: proof + memory analysis + raw costs ---------------
    # (cost variants below always use microbatches=1 — flop/byte totals are
    # microbatch-invariant, and the mb scan would hide them from
    # cost_analysis; the MAIN compile carries the memory effect.)
    t0 = time.perf_counter()
    jitted, args = build_jitted(cfg, shape, mesh, rules, window=window,
                                microbatches=microbatches)
    lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    ma = compiled.memory_analysis()
    raw = _cost_record(compiled)

    # ---- cost pass: unrolled 1/2-unit variants ---------------------------
    units = depth_units(cfg)
    if skip_costs:
        corr = dict(raw, per_layer_flops=None, per_layer_bytes=None)
    else:
        costs = []
        with Lmod.unroll_mode(True):
            for u in (1, 2):
                cfg_u = with_depth(cfg, u)
                j_u, a_u = build_jitted(cfg_u, shape, mesh, rules,
                                        window=window)
                costs.append(_cost_record(j_u.lower(*a_u).compile()))
        corr = corrected_costs(costs[0], costs[1], units)

    roof = analysis.Roofline(
        flops_per_device=corr["flops"], bytes_per_device=corr["bytes"],
        collective_bytes_per_device=float(sum(corr["coll"].values())),
        collectives=corr["coll"], n_devices=n_dev)

    model = build_model(cfg)
    n_total = pspec.n_params(model.param_specs())
    n_active = cfg.active_param_count() if cfg.is_moe else n_total
    mf = analysis.model_flops(cfg, shape, n_total, n_active)
    hlo_flops_total = roof.flops_per_device * n_dev
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "tiny": tiny, "profile": profile, "n_devices": n_dev,
        "window": window, "scan_units": units,
        "params_total": int(n_total), "params_active": int(n_active),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        },
        "roofline": roof.as_dict(),
        "raw_costs_scan_body_once": raw,
        "model_flops": mf,
        "useful_flops_ratio": (mf / hlo_flops_total
                               if hlo_flops_total else None),
        "lower_s": t_lower, "compile_s": t_compile,
    }
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())
    return rec


def format_line(rec: dict) -> str:
    r = rec["roofline"]
    return (f"mem/dev={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
            f"compute={r['compute_s']*1e3:.2f}ms "
            f"memory={r['memory_s']*1e3:.2f}ms "
            f"coll={r['collective_s']*1e3:.2f}ms "
            f"dom={r['dominant']} "
            f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)} "
            f"compile={rec['compile_s']:.0f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--profile", default="baseline", choices=list(PROFILES))
    ap.add_argument("--tiny", action="store_true",
                    help="8-device test mesh (set REPRO_DRYRUN_DEVICES=8)")
    ap.add_argument("--skip-costs", action="store_true",
                    help="main compile only (no unrolled cost variants)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches (train shapes)")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip combos whose JSON record already exists")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.out:
        os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                prof = args.profile + (f"_mb{args.microbatches}"
                                       if args.microbatches > 1 else "")
                tag = (f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}"
                       f"|{prof}")
                if args.out and args.skip_existing:
                    fn = tag.replace("|", "__").replace(".", "_") + ".json"
                    if os.path.exists(os.path.join(args.out, fn)):
                        print(f"SKIP {tag} (exists)", flush=True)
                        continue
                try:
                    t0 = time.perf_counter()
                    rec = dryrun_one(arch, shape, multi_pod=mp,
                                     tiny=args.tiny, profile=args.profile,
                                     save_hlo=args.save_hlo,
                                     skip_costs=args.skip_costs,
                                     microbatches=args.microbatches)
                    rec["profile"] = prof
                    rec["total_s"] = time.perf_counter() - t0
                    print(f"OK   {tag} {format_line(rec)} "
                          f"total={rec['total_s']:.0f}s", flush=True)
                    if args.out:
                        fn = tag.replace("|", "__").replace(".", "_") + ".json"
                        with open(os.path.join(args.out, fn), "w") as f:
                            json.dump(rec, f, indent=1)
                except Exception as e:
                    failures += 1
                    print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
