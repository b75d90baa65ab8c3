import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile one distributed CP-ALS iteration of a
paper workload per (workload x mesh) cell with 512 placeholder host devices,
record memory/cost/collective analysis and the three-term roofline of
:data:`MODELED_KIND`.  MUST set XLA_FLAGS before any other import (jax locks
the device count on first init) — hence the lines above.  It is a CPU
simulation by design: JAX_PLATFORMS=cpu keeps it (and the per-cell children
of ``--all``) off a TPU the host may hold.

  PYTHONPATH=src python -m repro.launch.dryrun --arch cpals-nell2
  PYTHONPATH=src python -m repro.launch.dryrun --all          # full matrix
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

from repro.core.coo import PAPER_DATASETS, PAPER_RANK, PAPER_WORKLOADS
from repro.launch.mesh import make_production_mesh
from repro.utils import roofline as RL

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# the chip whose published peaks bound the roofline (utils.roofline.PEAKS):
# the placeholder devices are host CPUs, so the kind is named, not detected
MODELED_KIND = "TPU v5 lite"


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
    from repro.api import (DataConfig, MethodConfig, PlanConfig, RunConfig,
                           Session)

    dataset = PAPER_WORKLOADS[workload]
    scale = min(1.0, nnz_cap / PAPER_DATASETS[dataset][1])
    cfg = RunConfig(
        data=DataConfig(dataset=dataset, scale=scale, cache=cache),
        plan=PlanConfig(policy=policy),
        method=MethodConfig(name=method, rank=PAPER_RANK))
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
        dims = PAPER_DATASETS[PAPER_WORKLOADS[workload]][0]
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
    cells = [(wl, mp) for wl in PAPER_WORKLOADS for mp in (False, True)]
    todo = []
    for wl, mp in cells:
        name = f"{wl}__iteration__{'multi' if mp else 'single'}"
        if resume and (out_dir / f"{name}.json").exists():
            continue
        todo.append((wl, mp))
    print(f"[dryrun] {len(todo)} cells to run ({len(cells) - len(todo)} cached)")

    procs: list[tuple[subprocess.Popen, str]] = []
    for wl, mp in todo:
        args = [sys.executable, "-m", "repro.launch.dryrun", "--arch", wl,
                "--mesh", "multi" if mp else "single", "--out", str(out_dir)]
        while len(procs) >= jobs:
            procs = _reap(procs)
            time.sleep(0.5)
        p = subprocess.Popen(args, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        procs.append((p, f"{wl}/{mp}"))
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
    ap.add_argument("--arch", choices=tuple(PAPER_WORKLOADS),
                    help="the paper workload to dry-run")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--out", type=Path, default=ARTIFACTS)
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cell overrides key=value (shard_c, mode_order, "
                    "impl, method)")
    args = ap.parse_args()

    if args.all:
        run_all(args.out, jobs=args.jobs)
        return
    if args.arch is None:
        ap.error("--arch or --all is required")

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = json.loads(v)

    run_cpals(args.arch, multi_pod=args.mesh == "multi", out_dir=args.out,
              shard_c=bool(overrides.get("shard_c")),
              mode_order=overrides.get("mode_order", "natural"),
              impl=overrides.get("impl", "auto"),
              method=overrides.get("method", "cp_als"),
              tag=args.tag)


if __name__ == "__main__":
    main()
