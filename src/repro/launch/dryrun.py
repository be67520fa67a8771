import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each
cell we jit the real step function with production in/out shardings,
lower against ShapeDtypeStruct inputs (no allocation), compile, and
record memory_analysis / cost_analysis / the collective schedule parsed
from the compiled per-device HLO.  Failures here (sharding mismatch, OOM
at compile, unsupported collective) are bugs in the system.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]   # every runnable cell
Results land in reports/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import SHAPES  # noqa: E402
from repro.configs.registry import ARCH_NAMES, cell_status, effective_shape, get_config  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.optim import OptConfig  # noqa: E402

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "reports", "dryrun")

#: per-arch optimizer policy (DESIGN.md §5: trillion-param MoEs need
#: factored/low-precision optimizer state to fit 16 GB/chip)
OPT_POLICY = {
    "kimi-k2-1t-a32b": OptConfig(optimizer="adafactor"),
    "jamba-1.5-large-398b": OptConfig(optimizer="adamw", moment_dtype="bfloat16"),
}

_COLL_APPLY_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective traffic from the compiled (SPMD) HLO.

    The scheduled HLO elides operand types, so we read the RESULT shape
    and derive operand bytes per op semantics:
      all-gather:      operand = result / group   (result is concatenated)
      all-reduce:      operand = result
      reduce-scatter:  operand = result * group
      all-to-all:      operand = result
      collective-permute: operand = result
    wire_bytes additionally estimates ring-algorithm link traffic.
    """
    out: dict[str, int] = {}
    wire: dict[str, float] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_APPLY_RE.search(line)
        if m is None or "-done" in line.split("=")[0]:
            continue
        result_ty, op = m.group(1), m.group(2)
        rbytes = sum(_bytes_of(d, s) for d, s in _SHAPE_RE.findall(result_ty))
        g = _group_size(line)
        if op == "all-gather":
            operand = rbytes // max(g, 1)
            w = rbytes * (g - 1) / max(g, 1)
        elif op == "all-reduce":
            operand = rbytes
            w = 2 * rbytes * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            operand = rbytes * g
            w = rbytes * (g - 1)
        else:  # all-to-all, collective-permute
            operand = rbytes
            w = rbytes * (g - 1) / max(g, 1) if op == "all-to-all" else rbytes
        out[op] = out.get(op, 0) + operand
        wire[op] = wire.get(op, 0.0) + w
        count[op] = count.get(op, 0) + 1
    out["total_bytes"] = sum(v for k, v in out.items() if k != "total_bytes")
    out["wire_bytes"] = round(sum(wire.values()))
    out["counts"] = count
    return out


def build_lowerable(cfg, shape, mesh):
    """(jitted_fn, example_args) for the step this shape implies."""
    opt_cfg = OPT_POLICY.get(cfg.name, OptConfig())
    spec = steps.input_specs(cfg, shape, opt_cfg)
    if shape.kind == "train":
        fn = steps.make_train_step(cfg, opt_cfg)
        in_sh = (
            shd.param_shardings(mesh, spec["params"]),
            shd.opt_shardings(mesh, spec["opt_state"]),
            shd.batch_shardings(mesh, spec["batch"]),
            shd.replicated(mesh),
        )
        out_sh = (in_sh[0], in_sh[1], shd.replicated(mesh))
        args = (spec["params"], spec["opt_state"], spec["batch"], spec["step"])
        # donate params/opt_state exactly as the production train loop does —
        # without it the dry-run double-counts the training state (in + out).
        return (
            jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(0, 1)),
            args,
        )
    elif shape.kind == "prefill":
        eff = effective_shape(cfg, shape)
        fn = steps.make_prefill_step(cfg, cache_len=eff.seq_len)
        cache_sh = shd.cache_shardings(mesh, steps.cache_specs(cfg, eff.global_batch, eff.seq_len))
        in_sh = (
            shd.param_shardings(mesh, spec["params"]),
            shd.batch_shardings(mesh, spec["batch"]),
        )
        # logits output: shard batch over dp, vocab over model
        from jax.sharding import PartitionSpec as P

        dp = shd.dp_axes(mesh) or None
        b = eff.global_batch
        logits_sh = shd.named(mesh, P(dp, "model"), (b, cfg.vocab_size))
        out_sh = (logits_sh, cache_sh)
        args = (spec["params"], spec["batch"])
    else:  # decode
        fn = steps.make_decode_step(cfg)
        from jax.sharding import PartitionSpec as P

        dp = shd.dp_axes(mesh) or None
        cache_sh = shd.cache_shardings(mesh, spec["caches"])
        b = shape.global_batch
        tok_sh = shd.named(mesh, P(dp), (b,))
        in_sh = (
            shd.param_shardings(mesh, spec["params"]),
            tok_sh,
            cache_sh,
            shd.replicated(mesh),
        )
        logits_sh = shd.named(mesh, P(dp, "model"), (b, cfg.vocab_size))
        out_sh = (logits_sh, cache_sh)
        args = (spec["params"], spec["token"], spec["caches"], spec["pos"])
        # serve loop donates the caches (in-place KV update)
        return (
            jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(2,)),
            args,
        )
    return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh), args


def _depth_variant(cfg, n_reps: int):
    """Unrolled n-pattern-rep config for exact per-layer HLO costing.

    XLA's cost_analysis visits while-loop (scan) bodies ONCE regardless of
    trip count (verified empirically), so the scanned model's numbers
    undercount by ~reps.  Costs are affine in depth, so two shallow
    unrolled lowerings give exact totals:
        total = c(1) + (reps - 1) * (c(2) - c(1)).
    """
    plen = len(cfg.pattern())
    over = dict(num_layers=plen * n_reps, scan_layers=False, name=cfg.name)
    if cfg.encoder_layers:
        # whisper: encoder depth == decoder depth, one combined slope
        assert cfg.encoder_layers == cfg.reps
        over["encoder_layers"] = n_reps
    return dataclasses.replace(cfg, **over)


def cost_dict(cost) -> dict:
    """Compiled.cost_analysis() as a dict ({} when the backend has none)."""
    return cost or {}


def extrapolated_costs(cfg, shape, mesh) -> dict:
    samples = []
    for n in (1, 2):
        cfg_n = _depth_variant(cfg, n)
        jitted, args = build_lowerable(cfg_n, shape, mesh)
        with mesh:
            compiled = jitted.lower(*args).compile()
        cost = cost_dict(compiled.cost_analysis())
        coll = parse_collectives(compiled.as_text())
        samples.append(
            dict(
                flops=cost.get("flops", 0.0),
                bytes=cost.get("bytes accessed", 0.0),
                coll=coll["total_bytes"],
                wire=coll["wire_bytes"],
                by_op={k: v for k, v in coll.items() if k not in ("total_bytes", "wire_bytes", "counts")},
            )
        )
    c1, c2 = samples
    reps = cfg.reps

    def affine(a, b):
        return a + (reps - 1) * (b - a)

    by_op = {
        k: affine(c1["by_op"].get(k, 0), c2["by_op"].get(k, 0))
        for k in set(c1["by_op"]) | set(c2["by_op"])
    }
    return dict(
        flops_per_device=affine(c1["flops"], c2["flops"]),
        bytes_per_device=affine(c1["bytes"], c2["bytes"]),
        collective_bytes_per_device=affine(c1["coll"], c2["coll"]),
        wire_bytes_per_device=affine(c1["wire"], c2["wire"]),
        collective_by_op=by_op,
        method="unrolled-depth-extrapolation r1,r2",
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, report_dir: str = REPORT_DIR):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    status = cell_status(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    os.makedirs(report_dir, exist_ok=True)
    out_path = os.path.join(report_dir, cell_id + ".json")
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "status": status}
    if status != "run":
        print(f"[dryrun] {cell_id}: {status}")
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
        return record

    eff = effective_shape(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        jitted, args = build_lowerable(cfg, eff, mesh)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = cost_dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)
        record.update(
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            flops_per_device=cost.get("flops", 0.0),
            bytes_accessed_per_device=cost.get("bytes accessed", 0.0),
            collectives=coll,
            memory=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
                output_bytes=getattr(mem, "output_size_in_bytes", 0),
                temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
                peak_bytes=getattr(mem, "peak_memory_in_bytes", 0)
                or getattr(mem, "temp_size_in_bytes", 0),
                alias_bytes=getattr(mem, "alias_size_in_bytes", 0),
            ),
        )
        if not multi_pod:  # roofline table is single-pod; exact depth costs
            record["roofline_inputs"] = extrapolated_costs(cfg, eff, mesh)
        print(
            f"[dryrun] {cell_id}: OK  flops/dev={record['flops_per_device']:.3e} "
            f"coll={coll['total_bytes']:.3e}B  peak={record['memory']['peak_bytes']/2**30:.2f}GiB "
            f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)"
        )
        # the two required proofs:
        print("  memory_analysis:", record["memory"])
        print("  cost_analysis: flops=%.4e bytes=%.4e" % (
            record["flops_per_device"], record["bytes_accessed_per_device"]))
        if "roofline_inputs" in record:
            ri = record["roofline_inputs"]
            print(
                "  extrapolated: flops=%.4e bytes=%.4e coll=%.4e"
                % (ri["flops_per_device"], ri["bytes_per_device"], ri["collective_bytes_per_device"])
            )
    except Exception as e:  # noqa: BLE001
        record["status"] = f"FAIL: {type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {cell_id}: FAIL {type(e).__name__}: {str(e)[:400]}")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--report-dir", default=REPORT_DIR)
    args = ap.parse_args()
    if args.all:
        ok = True
        for arch in ARCH_NAMES:
            for shape_name in SHAPES:
                rec = run_cell(arch, shape_name, args.multi_pod, args.report_dir)
                ok &= not str(rec["status"]).startswith("FAIL")
        raise SystemExit(0 if ok else 1)
    assert args.arch and args.shape, "--arch/--shape or --all"
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.report_dir)
    raise SystemExit(0 if not str(rec["status"]).startswith("FAIL") else 1)


if __name__ == "__main__":
    main()
