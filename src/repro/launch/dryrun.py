"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
    * .lower().compile() must succeed on the 16x16 single-pod mesh AND the
      (2,16,16) multi-pod mesh for every runnable cell;
    * memory_analysis() proves the working set fits;
    * cost_analysis() + HLO collective parsing feed §Roofline.

Usage:
    python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--jobs-dir experiments/dryrun]

Each cell can run in a subprocess (--all) so a failure or OOM in one cell
never kills the sweep; results are cached incrementally as JSON.
"""
# The VERY FIRST lines, before ANY other import (jax locks device count on
# first init).  The placeholder pod is CPU devices: pin the platform so
# this process (and the --all children that inherit its environment)
# never claims an attached accelerator.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse     # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import subprocess   # noqa: E402
import sys          # noqa: E402
import time         # noqa: E402

import jax          # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import SHAPES, all_archs, get_arch, shape_skips  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_rules      # noqa: E402
from repro.models import build_model                                # noqa: E402
from repro.models import spec as S                                  # noqa: E402
from repro.train import optim as O                                  # noqa: E402
from repro.train import train_step as TS                            # noqa: E402

# ---------------------------------------------------------------------------
# Collective-byte extraction from (per-partition) compiled HLO
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.-]+)\s*\(.*\{\s*$")
_CALL_REFS = re.compile(
    r"(?:body|condition|to_apply|calls)=%?([\w.-]+)"
    r"|branch_computations=\{([^}]*)\}")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_OPLINE = re.compile(r"^(?:ROOT\s+)?%?([\w.-]+)\s*=\s*"
                     r"((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*))\s+"
                     r"([a-z0-9-]+)\(")
# operands may carry inline type annotations on older XLA text
# ("dot(f32[64,32]{1,0} %Arg_0.1, ...)"), bare names on newer
_DOT_OPERANDS = re.compile(
    r"\((?:[a-z0-9]+\[[0-9,]*\]\S*\s+)?%?([\w.-]+),"
    r"\s*(?:[a-z0-9]+\[[0-9,]*\]\S*\s+)?%?([\w.-]+)")
_LHS_CDIMS = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _dims_of(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


# ops that alias rather than move data
_ALIAS_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
              "constant", "iota", "while", "conditional", "after-all",
              "opt-barrier"}
_OPERANDS_RE = re.compile(r"%([\w.-]+)")


def _effective_bytes(op: str, typ: str, line: str, types: dict) -> int:
    """Bytes actually moved by this op (output bytes, with corrections):
    alias ops move nothing; dynamic-update-slice and scatter write only
    their update operand, not the whole buffer."""
    if op in _ALIAS_OPS:
        return 0
    if op in ("dynamic-update-slice", "scatter", "scatter-add"):
        args = line.split(op + "(", 1)
        if len(args) == 2:
            names = _OPERANDS_RE.findall(args[1].split(")", 1)[0])
            upd_idx = 1 if op == "dynamic-update-slice" else 2
            if len(names) > upd_idx and names[upd_idx] in types:
                return _shape_bytes(types[names[upd_idx]])
    return _shape_bytes(typ)


def analyze_hlo(hlo_text: str) -> dict:
    """Loop-aware analysis of partitioned HLO text.

    XLA's cost_analysis counts while bodies ONCE; here every computation's
    cost is multiplied by its execution count (from known_trip_count
    backend configs), giving per-device totals for:
      * flops         — 2*M*N*K summed over dot ops (matmul-dominated)
      * bytes_proxy   — sum of op output bytes outside fusion bodies,
                        x2 for read+write (HBM traffic proxy)
      * collectives   — output bytes by kind (link-traffic proxy)
    """
    comps: dict = {}
    entry = None
    cur = None
    # tensors below this size are treated as VMEM-resident within their
    # computation (fused / register-allocated on the TPU target); larger
    # outputs are assumed to round-trip HBM.
    HBM_THRESHOLD = 1 << 20
    for raw in hlo_text.splitlines():
        stripped = raw.strip()
        m = _COMP_HDR.match(stripped) if raw and not raw.startswith("  ") else None
        if m and "=" not in stripped.split("(")[0]:
            cur = m.group(2)
            comps[cur] = {"coll_bytes": {k: 0 for k in _COLLECTIVES},
                          "coll_counts": {k: 0 for k in _COLLECTIVES},
                          "flops": 0.0, "out_bytes": 0.0, "hbm_bytes": 0.0,
                          "edges": [], "fused": False, "types": {},
                          "fusion_ops": [], "root_dus_bytes": None}
            if m.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        c = comps[cur]
        om = _OPLINE.match(stripped)
        if om:
            name, typ, op = om.group(1), om.group(2), om.group(3)
            c["types"][name] = typ
            if op == "fusion":
                # resolved at totals time: an in-place DUS-rooted fusion
                # writes only its update slice, not the whole buffer
                fm = re.search(r"calls=%?([\w.-]+)", stripped)
                c["fusion_ops"].append(
                    (fm.group(1) if fm else "", _shape_bytes(typ)))
                nbytes = 0
            else:
                nbytes = _effective_bytes(op, typ, stripped, c["types"])
            if stripped.startswith("ROOT") and op in (
                    "dynamic-update-slice", "scatter", "scatter-add"):
                c["root_dus_bytes"] = _effective_bytes(
                    op, typ, stripped, c["types"])
            c["out_bytes"] += nbytes
            if nbytes >= HBM_THRESHOLD:
                c["hbm_bytes"] += nbytes
            base = op[:-6] if op.endswith("-start") else op
            if base in _COLLECTIVES and not op.endswith("-done"):
                c["coll_bytes"][base] += nbytes
                c["coll_counts"][base] += 1
            if op == "dot":
                dm = _DOT_OPERANDS.search(stripped)
                cm = _LHS_CDIMS.search(stripped)
                if dm and cm is not None:
                    lhs_type = c["types"].get(dm.group(1), "")
                    ldims = _dims_of(lhs_type)
                    cidx = [int(i) for i in cm.group(1).split(",") if i]
                    ksize = 1
                    for i in cidx:
                        if i < len(ldims):
                            ksize *= ldims[i]
                    out_elems = 1
                    for d in _dims_of(typ):
                        out_elems *= d
                    c["flops"] += 2.0 * out_elems * ksize
        trip = 1
        tm = _TRIP.search(stripped)
        if tm:
            trip = int(tm.group(1))
        is_fusion_line = " fusion(" in stripped or stripped.startswith("fusion(")
        for cmatch in _CALL_REFS.finditer(stripped):
            if cmatch.group(1):
                is_body = stripped[cmatch.start():cmatch.start() + 5] == "body="
                callee = cmatch.group(1)
                c["edges"].append((callee, trip if is_body else 1))
                if is_fusion_line and callee in comps:
                    comps[callee]["fused"] = True
                elif is_fusion_line:
                    c.setdefault("fused_callees", []).append(callee)
            elif cmatch.group(2):
                for br in re.findall(r"%?([\w.-]+)", cmatch.group(2)):
                    c["edges"].append((br, 1))
    # late fusion marks (callee defined after caller)
    for c in comps.values():
        for callee in c.get("fused_callees", []):
            if callee in comps:
                comps[callee]["fused"] = True
    # execution-count fixpoint over the call DAG
    mult = {name: 0.0 for name in comps}
    if entry is None and comps:
        entry = next(iter(comps))
    if entry:
        mult[entry] = 1.0
    for _ in range(64):
        new = {name: 0.0 for name in comps}
        if entry:
            new[entry] = 1.0
        for name, c in comps.items():
            for callee, factor in c["edges"]:
                if callee in new:
                    new[callee] += mult[name] * factor
        if new == mult:
            break
        mult = new
    flops = 0.0
    out_bytes = 0.0
    hbm_bytes = 0.0
    coll = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for name, c in comps.items():
        mlt = mult.get(name, 0.0)
        flops += c["flops"] * mlt
        if not c["fused"]:
            fusion_bytes = 0.0
            fusion_hbm = 0.0
            for callee, out_b in c["fusion_ops"]:
                dus = comps.get(callee, {}).get("root_dus_bytes")
                eff = dus if dus is not None else out_b
                fusion_bytes += eff
                if eff >= (1 << 20):
                    fusion_hbm += eff
            out_bytes += (c["out_bytes"] + fusion_bytes) * mlt
            hbm_bytes += (c["hbm_bytes"] + fusion_hbm) * mlt
        for k in _COLLECTIVES:
            coll[k] += int(c["coll_bytes"][k] * mlt)
            counts[k] += int(c["coll_counts"][k] * mlt)
    return {
        "flops": flops,
        "bytes_proxy": 2.0 * out_bytes,
        "bytes_hbm_est": 2.0 * hbm_bytes,
        "collectives": {"bytes": coll, "counts": counts,
                        "total_bytes": int(sum(coll.values()))},
    }


def collective_bytes(hlo_text: str) -> dict:
    return analyze_hlo(hlo_text)["collectives"]


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

# Gradient-accumulation factors for the train shape: chosen so per-device
# activation live-sets fit 16 GB v5e HBM (validated via memory_analysis in
# EXPERIMENTS.md §Dry-run).  Must divide global_batch/batch_shards.
TRAIN_MICROBATCHES = {
    "rwkv6-1.6b": 2,
    "internvl2-2b": 2,
    "granite-moe-3b-a800m": 2,
    "olmoe-1b-7b": 2,
    "granite-8b": 2,
    "mistral-large-123b": 8,
    "granite-34b": 4,
    "olmo-1b": 1,
    "jamba-v0.1-52b": 8,
    "hubert-xlarge": 2,
}


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               opt_overrides: dict | None = None,
               arch_overrides: dict | None = None):
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        cfg = cfg.replace(microbatches=TRAIN_MICROBATCHES.get(cfg.name, 1))
    if arch_overrides:
        import jax.numpy as jnp
        conv = {}
        for k, v in arch_overrides.items():
            if k.endswith("dtype") and isinstance(v, str):
                v = jnp.dtype(v).type if hasattr(jnp, v) is False else getattr(jnp, v)
            conv[k] = v
        cfg = cfg.replace(**conv)
    skip = shape_skips(cfg, shape)
    if skip:
        return {"status": "skip", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = mesh_rules(multi_pod)
    cfg = cfg.replace(spmd_constraints=True,
                      mesh_axis_sizes=tuple(mesh.shape.items()))
    model = build_model(cfg)
    pshard = TS.param_shardings(model, mesh, rules)
    abs_params = model.abstract_params()

    if shape.kind == "train":
        opt_cfg = O.AdamWConfig(**(opt_overrides or {}))
        step = TS.make_train_step(model, opt_cfg)
        oshard = TS.opt_state_shardings(model, opt_cfg, mesh, rules)
        bshard = TS.batch_shardings(model, shape, mesh, rules)
        abs_opt = jax.eval_shape(lambda p: O.adamw_init(opt_cfg, p), abs_params)
        abs_batch = model.input_specs(shape)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            ).lower(abs_params, abs_opt, abs_batch)
    elif shape.kind == "prefill":
        step = TS.make_serve_step(model, "prefill")
        bshard = TS.batch_shardings(model, shape, mesh, rules)
        cshard = TS.prefill_cache_shardings(model, shape, mesh, rules)
        abs_batch = model.input_specs(shape)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step, in_shardings=(pshard, bshard),
                out_shardings=(None, cshard),
            ).lower(abs_params, abs_batch)
    else:  # decode
        step = TS.make_serve_step(model, "decode")
        bsh = TS.batch_shardings(model, shape, mesh, rules)
        specs = model.input_specs(shape)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(pshard, bsh["cache"], bsh["tokens"], bsh["pos"]),
                out_shardings=(None, bsh["cache"]),
                donate_argnums=(1,),
            ).lower(abs_params, specs["cache"], specs["tokens"], specs["pos"])
    return {"status": "lowered", "lowered": lowered, "model": model,
            "mesh": mesh, "cfg": cfg, "shape": shape}


def analyze_cell(arch: str, shape_name: str, multi_pod: bool,
                 opt_overrides: dict | None = None,
                 arch_overrides: dict | None = None) -> dict:
    t0 = time.time()
    res = lower_cell(arch, shape_name, multi_pod, opt_overrides,
                     arch_overrides)
    if res["status"] == "skip":
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skip", "reason": res["reason"]}
    lowered, model = res["lowered"], res["model"]
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hstats = analyze_hlo(hlo)
    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "compile_seconds": round(compile_s, 1),
        "n_devices": int(np.prod(list(res["mesh"].shape.values()))),
        "params_total": model.param_count(),
        "params_active": model.active_param_count(),
        # loop-corrected per-device numbers from the HLO walk
        "flops": hstats["flops"],
        "bytes_proxy": hstats["bytes_proxy"],
        "bytes_hbm_est": hstats["bytes_hbm_est"],
        # XLA's own (loop-body-once) numbers, for reference
        "xla_flops": float(cost.get("flops", 0.0)) if cost else None,
        "xla_bytes_accessed": (float(cost.get("bytes accessed", 0.0))
                               if cost else None),
        "collectives": hstats["collectives"],
        "memory": _memory_dict(mem),
        "hlo_bytes": len(hlo),
    }
    return out


def _memory_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "host_generated_code_size_in_bytes",
            "host_argument_size_in_bytes", "host_output_size_in_bytes",
            "host_temp_size_in_bytes", "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_one(args) -> int:
    result = analyze_cell(args.arch, args.shape, args.mesh == "multi",
                          arch_overrides=json.loads(args.overrides or "{}"))
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0 if result["status"] in ("ok", "skip") else 1


def run_all(args) -> int:
    os.makedirs(args.jobs_dir, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    for arch in all_archs():
        for shape in SHAPES:
            for mesh in meshes:
                cells.append((arch, shape, mesh))
    failures = 0
    for arch, shape, mesh in cells:
        name = f"{arch}__{shape}__{mesh}".replace("/", "_")
        path = os.path.join(args.jobs_dir, name + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[cached] {name}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mesh,
               "--out", path]
        if args.overrides:
            cmd += ["--overrides", args.overrides]
        print(f"[run] {name}", flush=True)
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=args.timeout,
                              env={**os.environ, "PYTHONPATH": "src"})
        dt = time.time() - t0
        if proc.returncode != 0:
            failures += 1
            err = (proc.stderr or "")[-2000:]
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                           "status": "fail", "error": err}, f, indent=1)
            print(f"[FAIL {dt:.0f}s] {name}\n{err}", flush=True)
        else:
            print(f"[ok {dt:.0f}s] {name}", flush=True)
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--overrides", default=None,
                    help='JSON ArchConfig overrides, e.g. {"moe_impl":"naive"}')
    ap.add_argument("--jobs-dir", default="experiments/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args))
    assert args.arch and args.shape and args.mesh in ("single", "multi")
    sys.exit(run_one(args))


if __name__ == "__main__":
    main()
