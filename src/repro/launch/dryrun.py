import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production meshes, and extract the roofline terms from the compiled
artifact (deliverables (e) and (g)).

MUST be run as its own process (the XLA flag above binds at first jax
init): ``PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b
--shape train_4k [--multi-pod] [--out artifacts/dryrun]``.

Per combo it records a JSON artifact with:
  * compiled cost_analysis flops / bytes accessed,
  * per-device peak memory from memory_analysis,
  * collective bytes by op kind, parsed from the post-SPMD HLO
    (convention: the *output* shape bytes of each collective op),
  * the three roofline terms in seconds for the hardware model
    (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI),
  * MODEL_FLOPS = 6*N*D (6*N_active*D for MoE) and the useful-compute
    ratio MODEL_FLOPS / HLO_FLOPs.
"""
import argparse
import dataclasses
import json
import re
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.config import INPUT_SHAPES, ArchConfig, InputShape
from repro.common.registry import get_arch, list_archs
from repro.launch.mesh import make_production_mesh
from repro.obs import get_logger

log = get_logger(__name__)

# hardware model (TPU v5e)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

# combos skipped by design (DESIGN.md §Arch-applicability)
LONG_CONTEXT_OK = ("h2o-danube-1.8b", "zamba2-7b", "gemma3-12b",
                   "mamba2-780m")


def skip_reason(cfg: ArchConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and cfg.name not in LONG_CONTEXT_OK:
        return ("pure full-attention arch: long_500k requires sub-quadratic "
                "attention (DESIGN.md §Arch-applicability)")
    return None


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(cfg: ArchConfig, shape: InputShape) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.frontend:
        tok = jax.ShapeDtypeStruct((b, s, cfg.frontend_dim), jnp.float32)
        tok1 = jax.ShapeDtypeStruct((b, 1, cfg.frontend_dim), jnp.float32)
    else:
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        tok1 = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tgt = jax.ShapeDtypeStruct((b, s), jnp.int32)
    msk = jax.ShapeDtypeStruct((b, s), jnp.float32)
    if shape.mode == "train":
        return {"batch": {"inputs": tok, "targets": tgt, "mask": msk}}
    if shape.mode == "prefill":
        return {"inputs": tok}
    if shape.mode == "decode":
        from repro.models.transformer import make_cache
        cache = jax.eval_shape(lambda: make_cache(cfg, b, s))
        pos = jax.ShapeDtypeStruct((b,), jnp.int32)
        return {"cache": cache, "tokens": tok1, "pos": pos}
    raise ValueError(shape.mode)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


def lower_combo(cfg: ArchConfig, shape: InputShape, mesh):
    """Returns the jax ``Lowered`` for the combo's step function."""
    from repro.serving.decode import (cache_shardings, make_decode_step,
                                      make_prefill_step, token_shardings)
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import (abstract_params, make_train_step,
                                        opt_shardings, param_shardings)
    from repro.train.optimizer import OptState

    pshape = abstract_params(cfg)
    with mesh:
        if shape.mode == "train":
            step, (ps, os_, bs) = make_train_step(
                mesh, cfg, AdamWConfig())
            params = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                pshape, ps)
            opt_abs = jax.eval_shape(
                lambda p: OptState(
                    step=jnp.zeros((), jnp.int32),
                    mu=jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.float32), p),
                    nu=jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.float32), p)),
                pshape)
            opt = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                opt_abs, os_)
            batch = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                input_specs(cfg, shape)["batch"], bs)
            return step.lower(params, opt, batch)

        if shape.mode == "prefill":
            step, (ps, ts) = make_prefill_step(
                mesh, cfg, batch=shape.global_batch, seq_len=shape.seq_len)
            params = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                pshape, ps)
            spec = input_specs(cfg, shape)
            inputs = jax.ShapeDtypeStruct(
                spec["inputs"].shape, spec["inputs"].dtype, sharding=ts)
            return step.lower(params, inputs)

        # decode
        step, (ps, cs, ts, pos_s) = make_decode_step(
            mesh, cfg, batch=shape.global_batch, max_seq=shape.seq_len)
        params = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh), pshape, ps)
        spec = input_specs(cfg, shape)
        cache = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            spec["cache"], cs)
        tokens = jax.ShapeDtypeStruct(spec["tokens"].shape,
                                      spec["tokens"].dtype, sharding=ts)
        pos = jax.ShapeDtypeStruct(spec["pos"].shape, spec["pos"].dtype,
                                   sharding=pos_s)
        return step.lower(params, cache, tokens, pos)


# ---------------------------------------------------------------------------
# artifact extraction
# ---------------------------------------------------------------------------

_HLO_SHAPE_RE = re.compile(
    r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+(" +
    "|".join(k.replace("-", "[-]") for k in COLLECTIVE_KINDS) + r")[\s(]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, list]:
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_HEAD_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Collective bytes by kind, **trip-count aware**.

    XLA's cost/byte attribution counts a while-loop body once, but every
    ``lax.scan`` over layers executes it L times. We split the module into
    computations, find ``while`` ops with their condition/body names, take
    the largest integer constant in the condition as the trip count (the
    scan bound — heuristic, documented in EXPERIMENTS.md), and multiply
    collective bytes inside each body accordingly (recursively, so chunked
    attention scans nested in layer scans compound).
    Convention: a collective's cost is its *output-shape* bytes.
    """
    comps = _split_computations(hlo_text)

    def direct_bytes(lines):
        out = {k: 0 for k in COLLECTIVE_KINDS}
        counts = {k: 0 for k in COLLECTIVE_KINDS}
        for line in lines:
            m = _HLO_SHAPE_RE.search(line)
            if not m:
                continue
            dtype, dims, kind = m.group(1), m.group(2), m.group(3)
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            out[kind] += n * _DTYPE_BYTES[dtype]
            counts[kind] += 1
        return out, counts

    def trip_count(cond_name: str) -> int:
        consts = []
        for line in comps.get(cond_name, []):
            consts += [int(c) for c in _CONST_RE.findall(line)]
        return max(consts) if consts else 1

    memo: Dict[str, Dict[str, int]] = {}

    def total(name: str, stack=()) -> Dict[str, int]:
        if name in memo:
            return memo[name]
        if name in stack:  # defensive: no recursion in valid HLO
            return {k: 0 for k in COLLECTIVE_KINDS}
        lines = comps.get(name, [])
        out, _ = direct_bytes(lines)
        for line in lines:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = trip_count(cond)
                sub = total(body, stack + (name,))
                for k in COLLECTIVE_KINDS:
                    out[k] += trips * sub[k]
        memo[name] = out
        return out

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HEAD_RE.match(line)
            if m:
                entry = m.group(1)
            break
    if entry is None:
        # fall back: flat sum (no loop multiplication)
        out, counts = direct_bytes(hlo_text.splitlines())
        out["counts"] = counts
        return out
    out = dict(total(entry))
    _, counts = direct_bytes(hlo_text.splitlines())
    out["counts"] = counts
    return out


def model_flops(cfg: ArchConfig, shape: InputShape) -> float:
    """6*N*D (N_active for MoE); D = tokens processed by the step."""
    n = cfg.active_param_count()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens   # forward only
    tokens = shape.global_batch   # one token per sequence
    return 2.0 * n * tokens


def analytic_flops(cfg: ArchConfig, shape: InputShape) -> float:
    """Analytic step FLOPs: param math + attention + SSD scan.

    XLA's cost_analysis counts while-loop bodies ONCE, so its FLOPs for a
    scanned-layer model are ~L x too small; the compute roofline term uses
    this analytic count instead (EXPERIMENTS.md §Roofline methodology).
    Training factor 4 = fwd + 2x bwd + ~1x remat recompute.
    """
    from repro.common.config import AttentionKind, BlockKind, SSMConfig
    b, s = shape.global_batch, shape.seq_len
    if shape.mode == "train":
        tokens, factor = b * s, 4.0
    elif shape.mode == "prefill":
        tokens, factor = b * s, 1.0
    else:
        tokens, factor = b, 1.0

    total = 2.0 * cfg.active_param_count() * tokens * factor

    hd = cfg.resolved_head_dim
    for idx, kind in enumerate(cfg.layer_kinds()):
        if kind in (BlockKind.ATTENTION, BlockKind.SHARED_ATTENTION):
            if shape.mode == "decode":
                ctx = float(s)
            elif cfg.attention_kind == AttentionKind.SLIDING:
                ctx = min(float(s) / 2, cfg.sliding_window)
            elif cfg.attention_kind == AttentionKind.LOCAL_GLOBAL:
                r = cfg.local_to_global_ratio
                is_global = (idx % (r + 1)) == r if r else True
                ctx = float(s) / 2 if is_global else min(
                    float(s) / 2, cfg.sliding_window)
            else:
                ctx = float(s) / 2  # causal average
            # QK^T and PV: 2 matmuls of [tokens, ctx] x hd per head
            total += 4.0 * tokens * ctx * cfg.num_heads * hd * factor
        elif kind == BlockKind.MAMBA2:
            scfg = cfg.ssm or SSMConfig()
            d_in = scfg.expand * cfg.d_model
            # SSD: B/C state projections plus intra-chunk matmuls
            total += 6.0 * tokens * d_in * scfg.state_dim * factor
    return total


def analytic_bytes(cfg: ArchConfig, shape: InputShape,
                   num_chips: int) -> float:
    """Analytic per-chip HBM traffic per step (napkin model, documented):

      weights: fwd reads params once (bf16); train adds grad write/read +
               f32 Adam m/v read+write + param write  (~22 bytes/param);
      activations: C_act * tokens * d_model * 2B per layer (C_act = 16
               train incl. remat recompute, 6 fwd-only);
      kv/ssm caches (decode): full cache read + point write.
    All sharded terms divide by the chip count.
    """
    from repro.common.config import BlockKind, SSMConfig
    b, s = shape.global_batch, shape.seq_len
    n_params = cfg.param_count()
    if shape.mode == "train":
        tokens, w_bytes, c_act = b * s, 22.0, 16.0
    elif shape.mode == "prefill":
        tokens, w_bytes, c_act = b * s, 2.0, 6.0
    else:
        tokens, w_bytes, c_act = b, 2.0, 6.0

    total = n_params * w_bytes
    total += c_act * tokens * cfg.d_model * 2.0 * cfg.num_layers

    if shape.mode == "decode":
        kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        for kind in cfg.layer_kinds():
            if kind in (BlockKind.ATTENTION, BlockKind.SHARED_ATTENTION):
                total += 2.0 * b * s * kvh * hd * 2.0   # read k+v cache
            elif kind == BlockKind.MAMBA2:
                scfg = cfg.ssm or SSMConfig()
                d_in = scfg.expand * cfg.d_model
                total += 2.0 * b * (d_in // scfg.head_dim) * \
                    scfg.state_dim * scfg.head_dim * 4.0  # rw ssm state
    return total / num_chips


def analyse(lowered, compiled, cfg: ArchConfig, shape: InputShape,
            num_chips: int) -> Dict:
    cost = compiled.cost_analysis()
    hlo_flops = float(cost.get("flops", 0.0))
    hlo_bytes = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    coll_total = sum(v for k, v in coll.items() if k != "counts")

    # Roofline terms. compute/memory use the analytic estimators because
    # XLA cost_analysis counts while-loop (scan) bodies once (~L x under-
    # count for scanned layers); the collective term uses the trip-count-
    # aware HLO parse (real compiled structure). All terms are per chip.
    a_flops = analytic_flops(cfg, shape)
    a_bytes = analytic_bytes(cfg, shape, num_chips)
    compute_s = a_flops / num_chips / PEAK_FLOPS
    memory_s = a_bytes / HBM_BW
    collective_s = coll_total / ICI_BW

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes +
                              ma.temp_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    mf = model_flops(cfg, shape)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    return {
        "arch": cfg.name,
        "shape": shape.name,
        "mode": shape.mode,
        "num_chips": num_chips,
        "analytic_flops_global": a_flops,
        "analytic_bytes_per_chip": a_bytes,
        "hlo_flops_per_chip_raw": hlo_flops,   # while bodies counted once
        "hlo_bytes_per_chip_raw": hlo_bytes,   # (recorded for reference)
        "collective_bytes_per_chip": coll_total,
        "collective_breakdown": coll,
        "memory": mem,
        "roofline": {**terms, "dominant": dominant},
        "model_flops_global": mf,
        "useful_compute_ratio": mf / a_flops if a_flops else 0.0,
    }


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: Optional[str]) -> Dict:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_tag = "multipod" if multi_pod else "pod"
    if reason:
        rec = {"arch": arch, "shape": shape_name, "skipped": reason,
               "mesh": mesh_tag}
        _save(rec, out_dir, arch, shape_name, mesh_tag)
        log.info(f"[dryrun] SKIP {arch} x {shape_name}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered = lower_combo(cfg, shape, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rec = analyse(lowered, compiled, cfg, shape, mesh.devices.size)
    rec.update({"mesh": mesh_tag, "lower_s": t_lower,
                "compile_s": t_compile})
    log.info(f"[dryrun] OK {arch} x {shape_name} [{mesh_tag}] "
          f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
          f"dominant={rec['roofline']['dominant']} "
          f"peak={rec['memory'].get('peak_bytes', 0)/2**30:.2f}GiB/chip")
    log.info(f"  memory_analysis: {rec['memory']}")
    log.info(f"  analytic: flops(global)={rec['analytic_flops_global']:.3e} "
          f"bytes/chip={rec['analytic_bytes_per_chip']:.3e} "
          f"coll/chip={rec['collective_bytes_per_chip']:.3e} "
          f"(hlo_raw flops/chip={rec['hlo_flops_per_chip_raw']:.2e})")
    _save(rec, out_dir, arch, shape_name, mesh_tag)
    return rec


def _save(rec: Dict, out_dir: Optional[str], arch: str, shape: str,
          mesh_tag: str) -> None:
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


# ---------------------------------------------------------------------------
# Pyramid search-step dry-run (the paper's own step on the production mesh)
# ---------------------------------------------------------------------------


def run_pyramid(multi_pod: bool, out_dir: Optional[str], *,
                naive: bool, n_per_shard: int = 1_000_000, d: int = 96,
                batch_per_replica: int = 256, k: int = 10,
                branching: int = 8) -> Dict:
    """Lower + compile Alg. 4 on the production mesh.

    Deployment model (paper Table I scale): Deep500M-like, 96-dim; one
    sub-HNSW shard per chip along the model axis x w_local, the data axis
    holds independent replica groups (the paper's replication). The naive
    baseline (HNSW-naive) sets capacity C = B; Pyramid routes to K of w.
    """
    from repro.common.config import PyramidConfig
    from repro.core.distributed import StackedShards, make_pyramid_search_fn
    from repro.core import hnsw as HN

    mesh = make_production_mesh(multi_pod=multi_pod)
    model_n = mesh.shape["model"]
    w = 16 * model_n  # 16 shards per model-axis chip
    cfg = PyramidConfig(metric="l2", num_shards=w, meta_size=10_000,
                        branching_factor=branching, capacity_factor=1.5,
                        ef_search=100)
    m0, mu, lpad, meta_m = 32, 16, 3, cfg.meta_size

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    stacked = StackedShards(
        data=sds((w, n_per_shard, d), jnp.float32),
        ids=sds((w, n_per_shard), jnp.int32),
        bottom=sds((w, n_per_shard, m0), jnp.int32),
        upper=sds((w, lpad, n_per_shard, mu), jnp.int32),
        entry=sds((w,), jnp.int32),
        num_upper_levels=sds((w,), jnp.int32))
    meta = HN.HNSWArrays(
        data=sds((meta_m, d), jnp.float32),
        ids=sds((meta_m,), jnp.int32),
        bottom=sds((meta_m, m0), jnp.int32),
        upper=sds((lpad, meta_m, mu), jnp.int32),
        entry=sds((), jnp.int32),
        num_upper_levels=sds((), jnp.int32))
    part = sds((meta_m,), jnp.int32)
    queries = sds((batch_per_replica * mesh.shape["data"] *
                   (mesh.shape.get("pod", 1) if multi_pod else 1), d),
                  jnp.float32)

    fn = make_pyramid_search_fn(
        mesh, cfg, k=k, batch=batch_per_replica, ef=100, max_iters=200,
        naive=naive, data_axis="data")
    with mesh:
        t0 = time.time()
        lowered = fn.lower(stacked, meta, part, queries)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    coll_total = sum(v for kk, v in coll.items() if kk != "counts")
    ma = compiled.memory_analysis()
    name = "pyramid_naive" if naive else "pyramid_routed"
    mesh_tag = "multipod" if multi_pod else "pod"
    rec = {
        "arch": name, "shape": f"search_b{batch_per_replica}", "mesh": mesh_tag,
        "num_chips": mesh.devices.size,
        "hlo_flops_per_chip_raw": float(cost.get("flops", 0.0)),
        "hlo_bytes_per_chip_raw": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_chip": coll_total,
        "collective_breakdown": coll,
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes +
                              ma.temp_size_in_bytes),
        },
        "lower_s": t_lower, "compile_s": t_compile,
        "capacity": "B" if naive else
            f"B*K/w*cf={batch_per_replica}*{branching}/{w}*1.5",
    }
    log.info(f"[dryrun] OK {name} [{mesh_tag}] lower={t_lower:.1f}s "
          f"compile={t_compile:.1f}s "
          f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB/chip "
          f"flops/chip(raw)={rec['hlo_flops_per_chip_raw']:.3e}")
    _save(rec, out_dir, name, rec["shape"], mesh_tag)
    return rec


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch name or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--pyramid", action="store_true",
                    help="dry-run the Alg. 4 search step itself "
                         "(naive + routed) instead of the archs")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    if args.pyramid:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            for naive in (True, False):
                run_pyramid(mp, args.out, naive=naive)
        return

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_one(arch, shape, mp, args.out)
                except Exception as e:
                    failures.append((arch, shape, mp, repr(e)))
                    log.info(f"[dryrun] FAIL {arch} x {shape} "
                          f"{'multipod' if mp else 'pod'}: {e!r}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    log.info("[dryrun] all combos lowered + compiled OK")


if __name__ == "__main__":
    main()
