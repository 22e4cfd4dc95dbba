import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax  # noqa: E402
# The 512 fake devices are host devices: never take an attached TPU.
jax.config.update("jax_platforms", "cpu")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the right step function (train_step for train
shapes, prefill/serve_step for inference shapes) against abstract params
(ShapeDtypeStruct — nothing is allocated), compiles it for the production
mesh, and records memory_analysis / cost_analysis / per-collective bytes to
an incremental JSON the roofline report reads from.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.registry import (ARCHS, SHAPES, cell_supported, get_config,
                                    input_specs, is_encdec)
from repro.core.api import QuantConfig, integerize_params
from repro.distributed import sharding as shlib
from repro.distributed.sharding import (Rules, batch_specs, cache_specs,
                                        enforce_divisible, filter_mesh_axes,
                                        named_shardings, param_specs,
                                        use_rules, zero1_specs)


def _finalize(spec_tree, abs_tree, mesh):
    return named_shardings(
        enforce_divisible(filter_mesh_axes(spec_tree, mesh), abs_tree, mesh),
        mesh)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import encdec, lm
from repro.models import scan_util
from repro.optim import OptConfig, init_opt_state, opt_update

TRAIN_QUANT = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, mode="fake")
SERVE_QUANT = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, kv_bits=8,
                          mode="int")


def _batch_axes(mesh, global_batch):
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return tuple(axes) if (global_batch % n == 0 and global_batch >= n) \
        else ()


def _abstract(fn, *args):
    return jax.eval_shape(fn, *args)


def _make_cell(arch, shape, mesh, *, remat=True, expert_fsdp=None,
               for_cost=False):
    """Returns (step_fn, args_abs, in_shardings, donate) for one cell.

    ``for_cost=True`` builds the flop-accounting variant: same math, but
    full-attention archs use one query chunk (chunking doesn't change
    FLOPs and single-chunk lowering keeps the unrolled jaxpr small).
    """
    cfg0 = get_config(arch)
    seq, gb, kind = SHAPES[shape]
    key = jax.random.PRNGKey(0)
    encdec_arch = is_encdec(cfg0)
    if for_cost and not encdec_arch and cfg0.attn_window is None:
        cfg0 = cfg0.replace(q_chunk=max(seq, cfg0.q_chunk))
    if for_cost and encdec_arch:
        cfg0 = cfg0.replace(q_chunk=max(seq, cfg0.q_chunk))
    if expert_fsdp is None:
        expert_fsdp = (kind == "train")

    if kind == "train":
        cfg = cfg0.replace(quant=TRAIN_QUANT)
        if not encdec_arch:
            cfg = cfg.replace(remat=remat)
        params_abs = _abstract(
            lambda k: (encdec.init_params(k, cfg) if encdec_arch
                       else lm.init_params(k, cfg)), key)
        opt_abs = _abstract(init_opt_state, params_abs)
        ocfg = OptConfig(total_steps=10000)
        loss = encdec.loss_fn if encdec_arch else lm.lm_loss

        def train_step(params, opt_state, batch):
            (l, _), grads = jax.value_and_grad(
                lambda p, b: loss(p, b, cfg), has_aux=True)(params, batch)
            params, opt_state, om = opt_update(params, grads, opt_state, ocfg)
            return params, opt_state, l

        _, bspec_abs = input_specs(arch, shape, cfg)
        bax = _batch_axes(mesh, gb)
        data_size = mesh.shape.get("data", 1)
        ospecs = {"mu": zero1_specs(opt_abs["mu"],
                                    param_specs(opt_abs["mu"],
                                                expert_fsdp=expert_fsdp),
                                    data_size=data_size),
                  "nu": zero1_specs(opt_abs["nu"],
                                    param_specs(opt_abs["nu"],
                                                expert_fsdp=expert_fsdp),
                                    data_size=data_size),
                  "step": jax.sharding.PartitionSpec()}
        in_sh = (_finalize(param_specs(params_abs, expert_fsdp=expert_fsdp),
                           params_abs, mesh),
                 _finalize(ospecs, opt_abs, mesh),
                 _finalize(batch_specs(bspec_abs, bax), bspec_abs, mesh))
        return (train_step, (params_abs, opt_abs, bspec_abs), in_sh, (0, 1),
                bax, cfg)

    # Serving cells: integerized params.
    cfg = cfg0.replace(quant=SERVE_QUANT)
    iparams_abs = _abstract(
        lambda k: integerize_params(
            (encdec.init_params(k, cfg) if encdec_arch
             else lm.init_params(k, cfg)), SERVE_QUANT), key)
    bax = _batch_axes(mesh, gb)
    psh = _finalize(param_specs(iparams_abs), iparams_abs, mesh)

    if kind == "prefill":
        _, bspec_abs = input_specs(arch, shape, cfg)
        if encdec_arch:
            def step(params, batch):
                return encdec.prefill(params, batch, cfg)
        else:
            def step(params, batch):
                return lm.prefill(params, batch, cfg)
        in_sh = (psh, _finalize(batch_specs(bspec_abs, bax), bspec_abs, mesh))
        return step, (iparams_abs, bspec_abs), in_sh, (), bax, cfg

    # decode: one new token against a cache of length seq.
    if encdec_arch:
        cache_abs = _abstract(lambda: encdec.init_cache(cfg, gb, seq))
        def step(params, token, cache):
            return encdec.decode_step(params, token, cache, cfg)
    else:
        cache_abs = _abstract(lambda: lm.init_cache(cfg, gb, seq))
        def step(params, token, cache):
            return lm.decode_step(params, token, cache, cfg)
    _, bspec_abs = input_specs(arch, shape, cfg)
    tok_abs = bspec_abs["token"]
    in_sh = (psh,
             _finalize(batch_specs(tok_abs, bax), tok_abs, mesh),
             _finalize(cache_specs(cache_abs, bax), cache_abs, mesh))
    return step, (iparams_abs, tok_abs, cache_abs), in_sh, (2,), bax, cfg


def run_cell(arch, shape, mesh_kind, *, verbose=True, remat=True,
             expert_fsdp=None, variant=None):
    """``variant``: perf-iteration knobs — "sp" (Megatron-SP residual),
    "packed" (int4 nibble-packed weights), "nofsdp" (experts replicated
    over data)."""
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "variant": variant or "baseline"}
    ok, why = cell_supported(arch, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        if variant == "nofsdp":
            expert_fsdp = False
        global SERVE_QUANT
        old_sq = SERVE_QUANT
        if variant in ("packed", "opt"):
            SERVE_QUANT = SERVE_QUANT.replace(pack_weights=True)
        if variant == "kv4":
            SERVE_QUANT = SERVE_QUANT.replace(pack_weights=True, kv_bits=4)
        try:
            step, args_abs, in_sh, donate, bax, cfg = _make_cell(
                arch, shape, mesh, remat=remat, expert_fsdp=expert_fsdp)
        finally:
            SERVE_QUANT = old_sq
        seq, gb, kind = SHAPES[shape]
        rules = Rules(batch=bax or (),
                      seq_tp=("model",) if variant == "sp" else (),
                      mesh=mesh,
                      int_bf16_reduce=(variant in ("bf16red", "opt")),
                      moe_a2a=(variant in ("a2a", "opt")),
                      expert_fsdp=(expert_fsdp if expert_fsdp is not None
                                   else kind == "train"))
        with mesh, use_rules(rules):
            jitted = jax.jit(step, in_shardings=in_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args_abs)
            compiled = lowered.compile()
            rec["memory"] = hlo_analysis.memory_dict(compiled)
            rec["cost"] = hlo_analysis.cost_dict(compiled)
            hlo_txt = compiled.as_text()
            rec["collectives"] = hlo_analysis.collective_bytes(hlo_txt)
            rec["collectives_scaled"] = \
                hlo_analysis.collective_bytes_scaled(hlo_txt)
        # FLOP-accounting pass: unsharded lowering with scans unrolled so
        # HloCostAnalysis sees every layer (lowering only, never compiled).
        step_c, args_c, *_ = _make_cell(arch, shape, mesh, remat=remat,
                                        expert_fsdp=expert_fsdp,
                                        for_cost=True)
        with scan_util.full_unroll():
            lowered_c = jax.jit(step_c).lower(*args_c)
        ca = lowered_c.cost_analysis() or {}
        rec["cost_unrolled"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))}
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-2000:]
    rec["seconds"] = round(time.time() - t0, 1)
    if verbose:
        flops = rec.get("cost", {}).get("flops", 0)
        print(f"[dryrun] {arch} x {shape} x {mesh_kind}: {rec['status']} "
              f"({rec['seconds']}s, flops={flops:.3g})", flush=True)
        if rec["status"] == "error":
            print(rec["error"], flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="perf variant: sp|bf16red|packed|nofsdp|opt")
    args = ap.parse_args(argv)

    archs = [a for a in ARCHS if a != "deit-s"] if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                cell_key = f"{arch}|{shape}|{mesh_kind}"
                prev = results.get(cell_key)
                if prev and prev.get("status") in ("ok", "skipped") \
                        and not args.force:
                    continue
                rec = run_cell(arch, shape, mesh_kind,
                               remat=not args.no_remat,
                               variant=args.variant)
                rec.pop("trace", None) if rec.get("status") == "ok" else None
                results[cell_key] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
