"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the real
train/prefill/serve step with full shardings, compiles, and records
memory/cost/collective analyses for the roofline (EXPERIMENTS.md).

Usage:
    python -m repro.launch.dryrun                    # all cells, both meshes
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --multi-pod        # 2x16x16 cells only
"""
# The two lines below MUST run before any other import (jax locks the
# device count at first init).
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ALIASES, ARCH_IDS, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh        # noqa: E402
from repro.models import (SHAPES_BY_NAME, applicable_shapes, decode_step,
                          init_cache, init_params, prefill, set_mesh)  # noqa: E402
from repro.models.config import ModelConfig, ShapeSpec    # noqa: E402
from repro.sharding import (batch_axes, batch_sharding, cache_shardings,
                            dp_axes, tree_shardings)               # noqa: E402
from repro.training import AdamW, input_specs, make_train_state, make_train_step  # noqa: E402

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2}

_COLL_RE = re.compile(
    r"= \(?([a-z0-9]+\[[0-9,]*\][^)]*?)\)?\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", )
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> dict:
    """Per-device collective traffic from the post-SPMD HLO (result-shape
    proxy; all-reduce counted 2x for the ring reduce+broadcast)."""
    out = {}
    bytes_total = 0.0
    for m in _COLL_RE.finditer(hlo):
        shapes, op = m.group(1), m.group(2)
        b = _shape_bytes(shapes)
        factor = 2.0 if op == "all-reduce" else 1.0
        key = op
        out[key] = out.get(key, {"count": 0, "bytes": 0})
        out[key]["count"] += 1
        out[key]["bytes"] += int(b * factor)
        bytes_total += b * factor
    out["total_bytes"] = int(bytes_total)
    return out


def cost_analysis_dict(compiled) -> dict:
    """The flat {counter: value} dict of ``Compiled.cost_analysis()``."""
    return compiled.cost_analysis()


def _arch_cfg(arch: str) -> ModelConfig:
    return get_config(arch)


def build_lowerable(cfg: ModelConfig, shape: ShapeSpec, mesh):
    """Returns (fn, args_sds, in_shardings, out_shardings, donate)."""
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if shape.kind == "train":
        opt = AdamW()
        state_sds = jax.eval_shape(
            lambda k: make_train_state(init_params(k, cfg), opt),
            jax.random.PRNGKey(0))
        state_sh = tree_shardings(state_sds, cfg, mesh)
        batch_sds = input_specs(cfg, shape)
        batch_sh = batch_sharding(batch_sds, mesh, axes=dp_axes(cfg, mesh))
        fn = make_train_step(cfg, opt, microbatches=cfg.train_microbatches,
                             grad_shardings=state_sh.params)
        return fn, (state_sds, batch_sds), (state_sh, batch_sh), \
            (state_sh, None), (0,)
    params_sds = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    params_sh = tree_shardings(params_sds, cfg, mesh)
    if shape.kind == "prefill":
        spec = input_specs(cfg, shape)
        tok_sh = batch_sharding(spec["tokens"], mesh)
        extra_sh = batch_sharding(spec["extra"], mesh) \
            if spec["extra"] is not None else None
        cache_out_sds = jax.eval_shape(
            lambda p, t, e: prefill(p, t, cfg, extra=e),
            params_sds, spec["tokens"], spec["extra"])[1]
        cache_sh = cache_shardings(cache_out_sds, cfg, mesh, shape)
        fn = lambda p, t, e: prefill(p, t, cfg, extra=e)
        return fn, (params_sds, spec["tokens"], spec["extra"]), \
            (params_sh, tok_sh, extra_sh), (None, cache_sh), ()
    # decode
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
    cache_sh = cache_shardings(cache_sds, cfg, mesh, shape)
    tok_sds = input_specs(cfg, shape)["tokens"]
    tok_sh = batch_sharding(tok_sds, mesh)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    fn = lambda p, c, t, pos: decode_step(p, c, t, pos, cfg)
    return fn, (params_sds, cache_sds, tok_sds, pos_sds), \
        (params_sh, cache_sh, tok_sh, NamedSharding(mesh, P())), \
        (None, cache_sh), (1,)


def _apply_overrides(cfg: ModelConfig, overrides: dict) -> ModelConfig:
    """Flat (remat=full) and nested (xlstm.chunk=64) config overrides."""
    import dataclasses
    flat = {k: v for k, v in overrides.items() if "." not in k}
    if flat:
        cfg = cfg.with_(**flat)
    for k, v in overrides.items():
        if "." in k:
            sub, field_ = k.split(".", 1)
            cfg = cfg.with_(**{sub: dataclasses.replace(
                getattr(cfg, sub), **{field_: v})})
    return cfg


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict = None) -> dict:
    cfg = _arch_cfg(arch)
    if overrides:
        cfg = _apply_overrides(cfg, overrides)
    shape = SHAPES_BY_NAME[shape_name]
    if shape.name == "long_500k" and not cfg.subquadratic:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "full-attention arch; 500k decode is out of family "
                          "contract (DESIGN.md #4)"}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh, dp_axes(cfg, mesh))
    fn, args, in_sh, out_sh, donate = build_lowerable(cfg, shape, mesh)
    with mesh:
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    res = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "status": "ok", "mesh": dict(mesh.shape),
           "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1)}
    try:
        ma = compiled.memory_analysis()
        res["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        res["memory"] = {"error": repr(e)[:200]}
    try:
        ca = cost_analysis_dict(compiled)
        res["cost"] = {k: float(v) for k, v in ca.items()
                       if k in ("flops", "bytes accessed", "transcendentals",
                                "bytes accessed output", "optimal_seconds")}
    except Exception as e:  # pragma: no cover
        res["cost"] = {"error": repr(e)[:200]}
    try:
        hlo = compiled.as_text()
        res["collectives"] = collective_stats(hlo)
        from repro.launch import hlo_analysis
        res["scan_aware"] = hlo_analysis.analyze(hlo)
    except Exception as e:  # pragma: no cover
        res["collectives"] = {"error": repr(e)[:200]}
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true", help="re-run cached cells")
    ap.add_argument("--set", nargs="*", default=[], metavar="K=V",
                    help="config overrides, e.g. layout=fsdp remat=full")
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                v = {"true": True, "false": False}.get(v.lower(), v)
        overrides[k] = v

    archs = [ALIASES.get(args.arch, args.arch)] if args.arch else list(ARCH_IDS)
    pods = []
    if args.multi_pod or not args.single_pod:
        pods.append(True)
    if args.single_pod or not args.multi_pod:
        pods.insert(0, False)
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        cfg = _arch_cfg(arch)
        shapes = [args.shape] if args.shape else \
            [s.name for s in applicable_shapes(cfg)] + \
            (["long_500k"] if not cfg.subquadratic else [])
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}.{shape}.{'2pod' if mp else '1pod'}"
                if args.tag:
                    tag += f".{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {tag}")
                    continue
                print(f"[lower+compile] {tag} ...", flush=True)
                try:
                    res = run_cell(arch, shape, mp, overrides=overrides)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": repr(e)[:500],
                           "trace": traceback.format_exc()[-2000:]}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                extra = ""
                if status == "ok":
                    extra = (f" compile={res['compile_s']}s flops/dev="
                             f"{res['cost'].get('flops', 0):.3e} coll="
                             f"{res['collectives'].get('total_bytes', 0):.2e}B")
                print(f"  -> {status}{extra}", flush=True)
    print("dry-run complete; failures:", failures)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
