import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
512 placeholder host devices, record memory/cost/collective analysis and the
three-term roofline of :data:`MODELED_KIND`.  MUST set XLA_FLAGS before any
other import (jax locks the device count on first init) — hence the lines
above.  It is a CPU simulation by design: JAX_PLATFORMS=cpu keeps it (and
the per-cell children of ``--all``) off a TPU the host may hold.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all          # full matrix
  PYTHONPATH=src python -m repro.launch.dryrun --arch cpals-nell2  # paper's own workload
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.mesh import (batch_sharding, make_production_mesh, rules_for,
                               sharding_fn, spec_for)
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import Model
from repro.models.config import SHAPES, cell_is_skipped
from repro.models.params import ParamSpec, axes_tree
from repro.optim import OPTIMIZERS
from repro.utils import roofline as RL

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# the chip whose published peaks bound the roofline (utils.roofline.PEAKS):
# the placeholder devices are host CPUs, so the kind is named, not detected
MODELED_KIND = "TPU v5 lite"

# per-arch optimizer (Adafactor where AdamW state cannot fit the mesh)
ARCH_OPT = {"kimi-k2-1t-a32b": "adafactor"}


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _map_axes(shape_tree, axes_tree_, fn):
    """map fn(SDS_leaf, axes_tuple) over parallel trees (axes leaves are
    tuples, which are themselves pytrees — flatten explicitly)."""
    is_axes_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    s_leaves, td = jax.tree.flatten(shape_tree)
    a_leaves = jax.tree.flatten(axes_tree_, is_leaf=is_axes_leaf)[0]
    assert len(s_leaves) == len(a_leaves), (len(s_leaves), len(a_leaves))
    return jax.tree.unflatten(td, [fn(s, a) for s, a in zip(s_leaves, a_leaves)])


def abstract_cache(model: Model, mesh, rules, batch, cache_len, *, src_len=0,
                   cdtype):
    specs = model.cache_specs(batch, cache_len, src_len=src_len)

    def leaf(path, s: ParamSpec):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "slot_pos":
            dt = jnp.int32
        elif name in ("state", "h"):
            dt = jnp.float32
        else:
            dt = cdtype
        sh = jax.sharding.NamedSharding(mesh, spec_for(s.axes, s.shape, mesh, rules))
        return _sds(s.shape, dt, sh)

    return jax.tree_util.tree_map_with_path(
        leaf, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               overrides: dict | None = None, mesh=None):
    """Returns (lowered, meta) for one cell.  Override keys starting with
    'rules:' go to the sharding rules, the rest to the ModelConfig."""
    cfg = configs.get(arch)
    rule_ov = {}
    step_kw = {}
    if overrides:
        import dataclasses
        cfg_ov = {k: v for k, v in overrides.items()
                  if not k.startswith(("rules:", "steps:"))}
        rule_ov = {k[6:]: v for k, v in overrides.items() if k.startswith("rules:")}
        step_kw = {k[6:]: v for k, v in overrides.items() if k.startswith("steps:")}
        if cfg_ov:
            cfg = dataclasses.replace(cfg, **cfg_ov)
    shape = SHAPES[shape_name]
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, multi_pod=multi_pod, overrides=rule_ov or None)
    sfn = sharding_fn(mesh, rules)
    model = Model(cfg)

    # activation sharding constraints (keeps flash/MoE internals sharded)
    from repro.models.layers import set_sharding_hook

    def _hook(x, axes):
        spec = spec_for(axes, x.shape, mesh, rules)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))

    set_sharding_hook(_hook, mesh)

    params_abs = model.abstract(sfn)
    bshapes = configs.batch_shapes(cfg, shape)
    batch_abs = {k: _sds(sh, dt, batch_sharding(mesh, rules, kind, sh))
                 for k, (sh, dt, kind) in bshapes.items()}

    meta = {"arch": arch, "shape": shape_name,
            "mesh": dict(mesh.shape), "n_chips": mesh.devices.size,
            "fsdp": cfg.fsdp, "optimizer": None}

    if shape.kind == "train":
        opt_name = ARCH_OPT.get(arch, "adamw")
        meta["optimizer"] = opt_name
        optimizer = OPTIMIZERS[opt_name]()
        opt_shapes = jax.eval_shape(optimizer.init, params_abs)
        axes = axes_tree(model.param_specs())
        opt_axes = optimizer.state_axes(axes)
        opt_abs = _map_axes(opt_shapes, opt_axes,
                            lambda s, a: _sds(s.shape, s.dtype, sfn(a, s.shape)))
        step_abs = _sds((), jnp.int32)
        fn = make_train_step(model, optimizer, **step_kw)
        lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
            params_abs, opt_abs, batch_abs, step_abs)
        return lowered, meta

    src = configs.src_len(cfg, shape) if cfg.encdec else 0
    if shape.kind == "prefill":
        cache_abs = abstract_cache(model, mesh, rules, shape.global_batch,
                                   shape.seq_len, src_len=src, cdtype=cfg.cdtype)
        fn = make_prefill_step(model)
        lowered = jax.jit(fn, donate_argnums=(2,)).lower(
            params_abs, batch_abs, cache_abs)
        return lowered, meta

    # decode
    cache_abs = abstract_cache(model, mesh, rules, shape.global_batch,
                               shape.seq_len, src_len=src, cdtype=cfg.cdtype)
    tokens_abs = batch_abs["tokens"]
    pos_abs = _sds((), jnp.int32)
    positions_abs = batch_abs.get("positions")
    fn = make_serve_step(model)
    lowered = jax.jit(fn, donate_argnums=(2,)).lower(
        params_abs, tokens_abs, cache_abs, pos_abs, positions_abs)
    return lowered, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             overrides: dict | None = None, out_dir: Path = ARTIFACTS,
             tag: str = "") -> dict:
    skip = cell_is_skipped(arch, shape_name)
    cell_id = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
    if tag:
        cell_id += f"__{tag}"
    if skip:
        art = {"cell": cell_id, "skipped": skip}
        _write(out_dir, cell_id, art)
        print(f"[dryrun] {cell_id}: SKIP ({skip})")
        return art

    t0 = time.time()
    lowered, meta = build_cell(arch, shape_name, multi_pod=multi_pod,
                               overrides=overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]

    # Roofline cost probes (see DESIGN.md section 6)
    probe = _probe_costs(arch, shape_name, multi_pod=multi_pod,
                         overrides=overrides, cfg=cfg)
    rl = RL.analyze_values(
        flops=probe["flops"], bytes_accessed=probe["bytes"],
        wire_bytes=probe["wire"], collectives=probe["collectives"],
        n_chips=meta["n_chips"],
        model_flops=RL.model_flops_estimate(cfg, shape), kind=MODELED_KIND)

    art = {
        "cell": cell_id, **meta, "device_kind": MODELED_KIND,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_gib": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "roofline": rl.to_json(),
        "probe": {k: probe[k] for k in ("reps", "probe_compile_s")},
        "overrides": overrides or {},
    }
    _write(out_dir, cell_id, art)
    print(f"[dryrun] {cell_id}: ok  compile={t_compile:.1f}s  "
          f"dominant={rl.dominant}  bound={rl.bound_s*1e3:.2f}ms  "
          f"peak={art['memory']['peak_estimate_gib']}GiB  "
          f"kind={MODELED_KIND!r}")
    return art


def _probe_costs(arch: str, shape_name: str, *, multi_pod: bool,
                 overrides: dict | None, cfg) -> dict:
    """Compile k=1 / k=2 unrolled probes; extrapolate costs to full depth."""
    import dataclasses

    prefix, reps, suffix = cfg.layer_plan
    t0 = time.time()
    results = []
    for k in (1, 2):
        ov = dict(overrides or {})
        ov.update(
            num_layers=len(prefix) + k * len(cfg.pattern) + len(suffix),
            enc_layers=(k if cfg.encdec else 0),
            unroll_loops=True,
        )
        lowered, _ = build_cell(arch, shape_name, multi_pod=multi_pod,
                                overrides=ov)
        comp = lowered.compile()
        cost = comp.cost_analysis()
        colls = RL.parse_collectives(comp.as_text())
        results.append({
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "wire": sum(c["wire"] for c in colls),
            "summary": RL.collective_summary(colls),
        })
    r1, r2 = results

    def extrap(a, b):
        return a + (reps - 1) * (b - a)

    # per-kind collective extrapolation
    kinds = set(r1["summary"]) | set(r2["summary"])
    summary = {}
    for kind in kinds:
        s1 = r1["summary"].get(kind, {"count": 0, "bytes": 0.0, "wire": 0.0})
        s2 = r2["summary"].get(kind, {"count": 0, "bytes": 0.0, "wire": 0.0})
        summary[kind] = {f: extrap(s1[f], s2[f]) for f in ("count", "bytes", "wire")}

    return {
        "flops": extrap(r1["flops"], r2["flops"]),
        "bytes": extrap(r1["bytes"], r2["bytes"]),
        "wire": extrap(r1["wire"], r2["wire"]),
        "collectives": summary,
        "reps": reps,
        "probe_compile_s": round(time.time() - t0, 2),
    }


def plan_cpals_workload(workload: str, *, policy: str = "auto",
                        nnz_cap: int = 200_000, cache: str | None = None,
                        method: str = "cp_als"):
    """Plan a paper decomposition workload from a scaled synthetic replica.

    The dry-run never materializes the full tensor; per-mode statistics are
    shape/skew properties, so a scaled-density replica (capped at ``nnz_cap``
    non-zeros) is enough evidence for the planner's regime rules.  The
    replica goes through ``repro.ingest`` so stats are measured once (and,
    with ``cache=``, persist across dry-run invocations).

    ``method`` selects the registry entry whose kernel family is planned:
    the CP methods score the mttkrp registry at the workload's rank, Tucker
    scores the ttmc registry at each mode's Kronecker width (the
    kernel/width resolution lives in ``Session.plan`` — one place)."""
    from repro import configs
    from repro.api import (DataConfig, MethodConfig, PlanConfig, RunConfig,
                           Session)

    dims, nnz, rank = configs.CPALS_WORKLOADS[workload]
    scale = min(1.0, nnz_cap / nnz)
    cfg = RunConfig(
        data=DataConfig(dataset=configs.CPALS_DATASET[workload], scale=scale,
                        cache=cache),
        plan=PlanConfig(policy=policy),
        method=MethodConfig(name=method, rank=rank))
    return Session.from_config(cfg).plan()


def run_cpals(workload: str, *, multi_pod: bool, out_dir: Path = ARTIFACTS,
              shard_c: bool = False, mode_order: str = "natural",
              impl: str = "auto", tag: str = "",
              method: str = "cp_als") -> dict:
    """Dry-run the paper's own CP-ALS workload (distributed, medium-grained).

    The per-mode plan is derived from a scaled synthetic replica and threads
    into the lowered iteration (each mode's local MTTKRP strategy).  The
    lowered iteration is the shard_map CP-ALS body, so ``method`` must be
    distributed-capable (``MethodSpec.supports_dist``) — others are rejected
    up front with the capability listing, same as ``dist_cp_als``."""
    from repro.api import require_capability
    from repro.core.distributed import _local_impls_of, build_dist_cpals_lowered
    from repro.utils.report import plan_report

    # the one capability gate (repro.api.executor) — same error text as
    # Session.fit(executor="dist") and dist_cp_als
    require_capability(method, "dist")
    plan = plan_cpals_workload(workload, policy=impl, method=method)
    print(plan_report(plan, method=method))
    local_impls = _local_impls_of(plan)
    if mode_order == "auto":
        # the lowering sorts modes longest-first; realign the per-mode impls
        dims = configs.CPALS_WORKLOADS[workload][0]
        perm = sorted(range(3), key=lambda m: -dims[m])
        local_impls = tuple(local_impls[m] for m in perm)
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered, info = build_dist_cpals_lowered(workload, mesh, shard_c=shard_c,
                                             mode_order=mode_order,
                                             local_impls=local_impls)
    info["plan"] = {f"mode{p.mode}": p.impl for p in plan.modes}
    info["method"] = method
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    rl = RL.analyze(cost, hlo, n_chips=mesh.devices.size,
                    model_flops=info["model_flops"], kind=MODELED_KIND)
    cell_id = f"{workload}__iteration__{'multi' if multi_pod else 'single'}"
    if tag:
        cell_id += f"__{tag}"
    art = {
        "cell": cell_id, "arch": workload, "shape": "iteration",
        "device_kind": MODELED_KIND,
        "mesh": dict(mesh.shape), "n_chips": mesh.devices.size,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_gib": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "roofline": rl.to_json(), "info": {k: v for k, v in info.items()
                                           if k != "model_flops"},
    }
    _write(out_dir, cell_id, art)
    print(f"[dryrun] {cell_id}: ok  compile={t_compile:.1f}s  "
          f"dominant={rl.dominant}  bound={rl.bound_s*1e3:.2f}ms  "
          f"kind={MODELED_KIND!r}")
    return art


def _write(out_dir: Path, cell_id: str, art: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell_id}.json").write_text(json.dumps(art, indent=1))


def run_all(out_dir: Path, *, resume: bool = True, jobs: int = 1) -> None:
    """Full matrix via one subprocess per cell (fresh XLA state, resumable)."""
    cells = []
    for arch in configs.ARCH_NAMES:
        for shape in SHAPES:
            for mp in (False, True):
                cells.append((arch, shape, mp))
    for wl in configs.CPALS_WORKLOADS:
        for mp in (False, True):
            cells.append((wl, "cpals", mp))

    todo = []
    for arch, shape, mp in cells:
        suffix = "multi" if mp else "single"
        name = (f"{arch}__{shape}__{suffix}" if shape != "cpals"
                else f"{arch}__iteration__{suffix}")
        if resume and (out_dir / f"{name}.json").exists():
            continue
        todo.append((arch, shape, mp))
    print(f"[dryrun] {len(todo)} cells to run ({len(cells) - len(todo)} cached)")

    procs: list[tuple[subprocess.Popen, str]] = []
    for arch, shape, mp in todo:
        args = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch]
        if shape != "cpals":
            args += ["--shape", shape]
        args += ["--mesh", "multi" if mp else "single", "--out", str(out_dir)]
        while len(procs) >= jobs:
            procs = _reap(procs)
            time.sleep(0.5)
        p = subprocess.Popen(args, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        procs.append((p, f"{arch}/{shape}/{mp}"))
    while procs:
        procs = _reap(procs)
        time.sleep(0.5)


def _reap(procs):
    alive = []
    for p, name in procs:
        if p.poll() is None:
            alive.append((p, name))
        else:
            out = p.stdout.read() if p.stdout else ""
            status = "ok" if p.returncode == 0 else f"FAIL rc={p.returncode}"
            print(f"[dryrun/all] {name}: {status}")
            if p.returncode != 0:
                print(out[-3000:])
    return alive


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="arch id or cpals-<workload>")
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--out", type=Path, default=ARTIFACTS)
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (perf pass)")
    args = ap.parse_args()

    if args.all:
        run_all(args.out, jobs=args.jobs)
        return

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = json.loads(v)

    mp = args.mesh == "multi"
    if args.arch.startswith("cpals-"):
        run_cpals(args.arch, multi_pod=mp, out_dir=args.out,
                  shard_c=bool(overrides.get("shard_c")),
                  mode_order=overrides.get("mode_order", "natural"),
                  impl=overrides.get("impl", "auto"),
                  method=overrides.get("method", "cp_als"),
                  tag=args.tag)
    else:
        run_cell(args.arch, args.shape, multi_pod=mp,
                 overrides=overrides or None, out_dir=args.out, tag=args.tag)


if __name__ == "__main__":
    main()
