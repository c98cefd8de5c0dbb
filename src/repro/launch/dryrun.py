import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, prove the sharding config is coherent, and extract the
roofline terms from the compiled artifact.

MUST be run as its own process (``python -m repro.launch.dryrun``): the
XLA_FLAGS line above executes before any other import so the 512 placeholder
host devices exist when jax first initializes.  Results are written
incrementally to ``experiments/dryrun/*.json`` so interrupted sweeps resume.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import ARCH_IDS, SHAPES, get_config, shape_applicable  # noqa: E402
from ..models import build_model, input_specs  # noqa: E402
from ..models import model as model_lib  # noqa: E402
from ..train import optimizer as opt_lib  # noqa: E402
from ..train import schedule as sched_lib  # noqa: E402
from ..train.trainer import make_train_step  # noqa: E402
from . import sharding as shlib  # noqa: E402
from .hlo_analysis import (Roofline, analyze_hlo, collective_bytes,  # noqa: E402
                           xla_cost_analysis)
from .mesh import dp_axes, make_production_mesh  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# =============================================================================
# per-cell lowering
# =============================================================================
def _train_artifacts(cfg, shape, mesh):
    model = build_model(cfg)
    optimizer = opt_lib.get_optimizer(cfg.optimizer)
    lr_fn = sched_lib.warmup_cosine()

    params_sds = model_lib.params_specs(cfg)
    opt_sds = jax.eval_shape(optimizer.init, params_sds)
    state_sds = {"params": params_sds, "opt": opt_sds,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
    pspecs = shlib.param_specs(cfg, params_sds, mesh)
    ospecs = shlib.opt_state_specs(pspecs, params_sds, opt_sds)
    state_specs = {"params": pspecs, "opt": ospecs, "step": P()}

    grad_shardings = shlib.to_named(pspecs, mesh)
    step_fn = _raw_train_step(model, optimizer, lr_fn,
                              grad_shardings=grad_shardings)

    batch_sds = input_specs(cfg, shape)
    bspecs = shlib.batch_specs(cfg, shape, mesh, batch_sds)

    jitted = jax.jit(
        step_fn,
        in_shardings=(shlib.to_named(state_specs, mesh),
                      shlib.to_named(bspecs, mesh)),
        out_shardings=(shlib.to_named(state_specs, mesh), None),
        donate_argnums=(0,),
    )
    lowered = jitted.lower(state_sds, batch_sds)
    return lowered, {"state": (state_sds, state_specs), "batch": (batch_sds, bspecs)}


def _raw_train_step(model, optimizer, lr_fn, grad_shardings=None):
    """Full production step: microbatched grad accumulation (bounds live
    activation memory to one microbatch) + optimizer update.

    ``grad_shardings`` pins gradients (and therefore the accumulation
    buffers) to the parameter sharding: per-microbatch weight-grad partials
    reduce-scatter immediately instead of living replicated — without the
    pin, GSPMD keeps dW replicated over the FSDP axis and the accumulator
    read/write traffic multiplies by the DP degree."""
    mb = max(1, model.cfg.train_microbatches)

    def pin(grads):
        if grad_shardings is None:
            return grads
        return jax.lax.with_sharding_constraint(grads, grad_shardings)

    def step(state, batch):
        params = state["params"]
        if mb > 1:
            def reshape(x):
                return x.reshape(mb, x.shape[0] // mb, *x.shape[1:])
            micro = jax.tree.map(reshape, batch)

            def acc(carry, one):
                loss_sum, grad_sum = carry
                (loss, _), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, one)
                grads = pin(grads)
                return (loss_sum + loss,
                        pin(jax.tree.map(jnp.add, grad_sum, grads))), None

            zeros = pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros(()), zeros), micro)
            loss = loss / mb
            grads = jax.tree.map(lambda g: g / mb, grads)
        else:
            (loss, _), grads = jax.value_and_grad(
                model.loss_fn, has_aux=True)(params, batch)
            grads = pin(grads)
        lr = lr_fn(state["step"])
        new_params, new_opt = optimizer.update(grads, state["opt"], params, lr)
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1},
                {"loss": loss})
    return step


def _prefill_artifacts(cfg, shape, mesh):
    model = build_model(cfg)
    params_sds = model_lib.params_specs(cfg)
    pspecs = shlib.param_specs(cfg, params_sds, mesh)
    batch_sds = input_specs(cfg, shape)
    bspecs = shlib.batch_specs(cfg, shape, mesh, batch_sds)

    def serve_prefill(params, batch):
        return model.prefill(params, batch["tokens"], batch.get("memory"))

    jitted = jax.jit(serve_prefill,
                     in_shardings=(shlib.to_named(pspecs, mesh),
                                   shlib.to_named(bspecs, mesh)),
                     out_shardings=None)
    lowered = jitted.lower(params_sds, batch_sds)
    return lowered, {"params": (params_sds, pspecs), "batch": (batch_sds, bspecs)}


def _decode_artifacts(cfg, shape, mesh):
    model = build_model(cfg)
    params_sds = model_lib.params_specs(cfg)
    pspecs = shlib.param_specs(cfg, params_sds, mesh)
    batch_sds = input_specs(cfg, shape)
    bspecs = shlib.batch_specs(cfg, shape, mesh, batch_sds)
    cache_sds = model_lib.cache_specs(cfg, shape.global_batch, shape.seq_len)
    cspecs = shlib.cache_specs_tree(cfg, shape, mesh, cache_sds)

    def serve_step(params, batch, cache):
        return model.decode_step(params, batch["token"], cache,
                                 batch.get("memory"))

    jitted = jax.jit(serve_step,
                     in_shardings=(shlib.to_named(pspecs, mesh),
                                   shlib.to_named(bspecs, mesh),
                                   shlib.to_named(cspecs, mesh)),
                     out_shardings=(None, shlib.to_named(cspecs, mesh)),
                     donate_argnums=(2,))
    lowered = jitted.lower(params_sds, batch_sds, cache_sds)
    return lowered, {"params": (params_sds, pspecs), "batch": (batch_sds, bspecs),
                     "cache": (cache_sds, cspecs)}


# =============================================================================
# analyses
# =============================================================================
def _cost_dict(compiled) -> dict:
    try:
        ca = xla_cost_analysis(compiled)   # list/dict normalized across jax versions
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and np.isfinite(float(v))}
    except Exception as e:
        return {"error": str(e)}


def _memory_dict(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                out[attr] = int(v)
        if not out:
            out["repr"] = str(ma)
    except Exception as e:
        out["error"] = str(e)
    return out


def _sharded_arg_bytes(sds_specs: dict, mesh) -> dict:
    """Per-device bytes of each argument group under its PartitionSpec."""
    sizes = {}
    for group, (sds, specs) in sds_specs.items():
        total = 0
        flat_s = jax.tree.leaves(sds)
        flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for s, spec in zip(flat_s, flat_p):
            nbytes = int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize if s.shape else jnp.dtype(s.dtype).itemsize
            denom = 1
            for entry in tuple(spec):
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, tuple) else (entry,)):
                    denom *= mesh.shape[ax]
            total += nbytes // max(1, denom)
        sizes[group] = total
    return sizes


def _model_flops(cfg, shape) -> float:
    _, active = cfg.param_count()
    if shape.kind == "train":
        return 6.0 * active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * active * shape.global_batch * shape.seq_len
    return 2.0 * active * shape.global_batch          # decode: one token/seq


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             skip_compile: bool = False) -> dict:
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    t0 = time.monotonic()
    from ..models.sharding_ctx import activation_sharding
    with activation_sharding(mesh, shlib.effective_dp(cfg, mesh)):
        if shape.kind == "train":
            lowered, groups = _train_artifacts(cfg, shape, mesh)
        elif shape.kind == "prefill":
            lowered, groups = _prefill_artifacts(cfg, shape, mesh)
        else:
            lowered, groups = _decode_artifacts(cfg, shape, mesh)
    lower_s = time.monotonic() - t0

    result = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips, "status": "lowered", "lower_s": lower_s,
        "arg_bytes_per_device": _sharded_arg_bytes(groups, mesh),
    }
    if skip_compile:
        return result

    t1 = time.monotonic()
    compiled = lowered.compile()
    result["compile_s"] = time.monotonic() - t1
    result["status"] = "compiled"
    result["memory_analysis"] = _memory_dict(compiled)
    result["cost_analysis_raw"] = _cost_dict(compiled)   # loops-once, per-dev

    hlo = compiled.as_text()
    repeats, _ = cfg.repeats_and_tail()
    stats = analyze_hlo(hlo, default_trip=max(1, repeats))
    result["hlo_analysis"] = stats.to_dict()
    rl = Roofline(
        hlo_flops=stats.flops,
        hlo_bytes=stats.hbm_bytes_fused,   # TPU-fusion estimate (raw recorded too)
        wire_bytes=stats.wire_bytes,
        chips=chips,
        model_flops=_model_flops(cfg, shape),
    )
    result["roofline"] = rl.to_dict()
    result["roofline"]["hlo_bytes_raw_per_dev"] = stats.hbm_bytes
    return result


# =============================================================================
# the dataframe-pipeline dry-run (the paper's technique on the mesh)
# =============================================================================
def run_pipeline_cell(multi_pod: bool, rows: int = 1 << 22, cols: int = 256,
                      groups: int = 8) -> dict:
    """Lower the Fig.-6 operator mix (map + groupby(n) + groupby(1) + window)
    as one shard_map program over the production mesh: rows shard DP, columns
    shard "model"; the groupby combine is the psum the paper's shuffle became."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    dp = dp_axes(mesh)

    vals = jax.ShapeDtypeStruct((rows, cols), jnp.float32)
    codes = jax.ShapeDtypeStruct((rows,), jnp.int32)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(dp, "model"), P(dp)),
        out_specs=(P(dp, "model"), P(None, "model"), P(None, "model"),
                   P(dp, "model")),
        check_vma=False)
    def pipeline_step(v, c):
        # MAP: null-scrub (paper's map benchmark: isnull→fill)
        mapped = jnp.where(jnp.isnan(v), 0.0, v)
        # GROUPBY(n): local MXU one-hot partial + psum over the DP axes
        onehot = jax.nn.one_hot(c % groups, groups, dtype=jnp.float32)
        partial = jnp.einsum("rg,rc->gc", onehot, mapped)
        gb_n = jax.lax.psum(partial, dp)
        # GROUPBY(1): plain reduction
        gb_1 = jax.lax.psum(mapped.sum(axis=0, keepdims=True), dp)
        # WINDOW: local cumsum + exclusive cross-shard carry (order-exact)
        local = jnp.cumsum(mapped, axis=0)
        totals = jax.lax.all_gather(local[-1], dp, tiled=False)
        idx = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
            jax.lax.axis_index(dp[0]) * mesh.shape[dp[1]] + jax.lax.axis_index(dp[1]))
        nshards = totals.shape[0]
        mask = (jnp.arange(nshards) < idx).astype(jnp.float32)
        carry = jnp.einsum("s,sc->c", mask, totals)
        window = local + carry
        return mapped, gb_n, gb_1, window

    t0 = time.monotonic()
    lowered = jax.jit(pipeline_step).lower(vals, codes)
    compiled = lowered.compile()
    cost = _cost_dict(compiled)
    stats = collective_bytes(compiled.as_text())
    return {
        "arch": "dataframe-pipeline", "shape": f"rows{rows}_cols{cols}",
        "mesh": "multi" if multi_pod else "single", "chips": chips,
        "status": "compiled", "compile_s": time.monotonic() - t0,
        "cost_analysis": cost,
        "collectives": {"wire_bytes": stats.wire_bytes, "counts": stats.counts},
        "memory_analysis": _memory_dict(compiled),
    }


# =============================================================================
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="also dry-run the dataframe pipeline step")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if args.arch == "all" else tuple(args.arch.split(","))
    shapes = tuple(SHAPES) if args.shape == "all" else tuple(args.shape.split(","))
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip] {tag}")
                    continue
                print(f"[run ] {tag}", flush=True)
                try:
                    res = run_cell(arch, shape, mp)
                except Exception as e:
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": str(e),
                           "traceback": traceback.format_exc()}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                print(f"[done] {tag}: {res['status']}", flush=True)

    if args.pipeline:
        for mp in meshes:
            tag = f"pipeline__{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                continue
            res = run_pipeline_cell(mp)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            print(f"[done] {tag}: {res['status']}", flush=True)


if __name__ == "__main__":
    main()
