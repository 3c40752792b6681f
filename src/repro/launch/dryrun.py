import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape) cell, on both the single-pod
16×16 mesh and the 2×16×16 multi-pod mesh:

    lowered  = jax.jit(step, in_shardings=…).lower(*input_specs(cell))
    compiled = lowered.compile()
    print(compiled.memory_analysis(), compiled.cost_analysis())

Train cells lower the full ``train_step`` (loss → grads → AdamW); decode
cells lower ``serve_step`` (one token against a seq_len KV cache); prefill
cells lower the forward+last-logits step.  Failures here (sharding
mismatch, unsupported collective) are bugs in the system.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
        --shape train_4k [--multi-pod] [--all] [--json out.json]

NOTE: the XLA_FLAGS line above MUST run before any other jax import.
"""
import argparse
import json
import re
import time
from typing import Dict

import jax

from repro.configs import ARCH_IDS, SHAPE_SETS, get_config
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import make_shardings
from repro.models import transformer as tf
from repro.optim import adamw
from repro.train.step import make_train_step

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes_from_hlo(hlo: str) -> Dict[str, float]:
    """Sum output-shape bytes of every collective op in the post-SPMD HLO.

    Wire-cost weighting (ring algorithms) is applied in benchmarks/roofline:
    here we report raw per-op tensor bytes by collective kind.
    """
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                   "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "c64": 8}
    out: Dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo):
        kind, dt, dims = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * dtype_bytes.get(dt, 4)
        out[kind] = out.get(kind, 0.0) + b
    return out


def shape_for(cfg: ArchConfig, shape: ShapeSpec) -> ShapeSpec:
    return shape


def lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, verbose=True):
    """Lower + compile one cell; returns the analysis record."""
    sh = make_shardings(mesh)
    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = make_train_step(cfg, adamw(3e-4), sh)
            state = sp.train_state_sds(cfg, mesh)
            batch = sp.batch_specs(cfg, shape, mesh)
            lowered = jax.jit(step).lower(state, batch)
        elif shape.kind == "prefill":
            def prefill(params, batch):
                h, _ = tf.forward_hidden(
                    cfg, params, batch["tokens"], sh,
                    vision_embeds=batch.get("vision_embeds"),
                    frames=batch.get("frames"), remat=False)
                logits = h[:, -1:, :] @ params["lm_head"]
                return sh.act_btv(logits)
            params, _ = sp.param_sds(cfg, mesh)
            batch = sp.batch_specs(cfg, shape, mesh)
            batch.pop("labels")
            lowered = jax.jit(prefill).lower(params, batch)
        else:  # decode
            def serve_step(params, cache, tokens):
                return tf.decode_step(cfg, params, cache, tokens, sh)
            params, _ = sp.param_sds(cfg, mesh)
            cache = sp.cache_specs(cfg, shape, mesh)
            tokens = sp.decode_token_specs(cfg, shape, mesh)
            lowered = jax.jit(serve_step).lower(params, cache, tokens)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": dict(mesh.shape),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_accessed_per_device": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
    }
    if verbose:
        print(f"[dryrun] {cfg.name} × {shape.name} × mesh{tuple(mesh.shape.values())}"
              f" lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB"
              f" temp={ma.temp_size_in_bytes/2**30:.2f}GiB"
              f" out={ma.output_size_in_bytes/2**30:.2f}GiB (per device)")
        print(f"  cost_analysis: flops/dev={rec['flops_per_device']:.3e}"
              f" bytes/dev={rec['bytes_accessed_per_device']:.3e}")
        print(f"  collectives: { {k: f'{v:.3e}' for k, v in coll.items()} }")
    return rec


# ---------------------------------------------------------------------------
# Measurement mode — exact trip-count accounting (see DESIGN.md §7).
#
# XLA's cost_analysis counts while-loop bodies ONCE (trip counts are opaque
# to it), so scan-over-layers graphs report per-superstep costs.  For the
# roofline we therefore lower *unrolled* reduced-depth variants at 1 and 2
# depth units, and extrapolate:  corrected = C(1) + (C(2) − C(1))·(U − 1)
# where U = true depth in units.  Embedding/loss/optimizer costs live in
# C(1) once (correct); per-layer costs appear in the marginal term.  The
# production scan graphs remain the compile/memory artifact.
# ---------------------------------------------------------------------------

import dataclasses as _dc


def _unit_plan(cfg: ArchConfig):
    """Returns (cfg_at_1_unit, cfg_at_2_units, units_true)."""
    meas = dict(unroll_layers=True, unroll_inner=True, attn_chunk=4096,
                remat_groups=0, rwkv_chunk=64)
    if cfg.encoder_layers:  # whisper: one unit = 1 enc + 1 dec layer
        c1 = _dc.replace(cfg, n_layers=1, encoder_layers=1, **meas)
        c2 = _dc.replace(cfg, n_layers=2, encoder_layers=2, **meas)
        return c1, c2, float(cfg.n_layers)
    if cfg.block_pattern == "M" and cfg.shared_attn_every:  # zamba2 segment
        u = cfg.shared_attn_every
        c1 = _dc.replace(cfg, n_layers=u, **meas)
        c2 = _dc.replace(cfg, n_layers=2 * u, **meas)
        return c1, c2, cfg.n_layers / u
    if cfg.first_layer_dense_ffn:  # prefix stays in the fixed part
        c1 = _dc.replace(cfg, n_layers=2, **meas)
        c2 = _dc.replace(cfg, n_layers=3, **meas)
        return c1, c2, float(cfg.n_layers - 1)
    c1 = _dc.replace(cfg, n_layers=1, **meas)
    c2 = _dc.replace(cfg, n_layers=2, **meas)
    return c1, c2, float(cfg.n_layers)


def measure_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, verbose=True):
    """Corrected per-step flops / bytes / collective-bytes for one cell."""
    c1, c2, units = _unit_plan(cfg)
    r1 = lower_cell(c1, shape, mesh, verbose=False)
    r2 = lower_cell(c2, shape, mesh, verbose=False)

    def extrap(k1, k2):
        return k1 + (k2 - k1) * (units - 1.0)

    coll = {}
    for kind in set(r1["collective_bytes"]) | set(r2["collective_bytes"]):
        coll[kind] = max(extrap(r1["collective_bytes"].get(kind, 0.0),
                                r2["collective_bytes"].get(kind, 0.0)), 0.0)
    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": dict(mesh.shape),
        "measured": True,
        "units_true": units,
        "flops_per_device": extrap(r1["flops_per_device"],
                                   r2["flops_per_device"]),
        "bytes_accessed_per_device": extrap(r1["bytes_accessed_per_device"],
                                            r2["bytes_accessed_per_device"]),
        "collective_bytes": coll,
        "memory": r2["memory"],  # production memory comes from the scan graph
        "unit_records": [r1, r2],
    }
    if verbose:
        print(f"[measure] {cfg.name} × {shape.name}: "
              f"flops/dev={rec['flops_per_device']:.3e} "
              f"bytes/dev={rec['bytes_accessed_per_device']:.3e} "
              f"coll={ {k: f'{v:.2e}' for k, v in coll.items()} }")
    return rec


def run(arch_ids, shape_names, multi_pod: bool, out_json=None,
        also_single=True):
    records = []
    meshes = []
    if also_single:
        meshes.append(make_production_mesh(multi_pod=False))
    if multi_pod:
        meshes.append(make_production_mesh(multi_pod=True))
    for arch in arch_ids:
        cfg = get_config(arch)
        for shape in SHAPE_SETS:
            if shape_names and shape.name not in shape_names:
                continue
            ok, why = sp.cell_is_runnable(cfg, shape)
            if not ok:
                print(f"[dryrun] {arch} × {shape.name}: {why}")
                records.append({"arch": arch, "shape": shape.name,
                                "skipped": why})
                continue
            for mesh in meshes:
                records.append(lower_cell(cfg, shape, mesh))
    if out_json:
        with open(out_json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"[dryrun] wrote {len(records)} records to {out_json}")
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="also run the 2×16×16 multi-pod mesh")
    ap.add_argument("--single-only", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else None
    run(archs, shapes, multi_pod=args.multi_pod and not args.single_only,
        out_json=args.json)


if __name__ == "__main__":
    main()
