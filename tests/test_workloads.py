"""The paper's workload table (``repro.core.coo``): the data sets' published
shapes, their scaled replicas, and the launcher ids that name them on every
command line."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.coo import (PAPER_DATASETS, PAPER_RANK, PAPER_WORKLOADS,
                            paper_dataset)

ROOT = Path(__file__).resolve().parents[1]
KEY = jax.random.PRNGKey(0)


def run_module(argv, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", *argv], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=ROOT)


@pytest.mark.parametrize("name", sorted(PAPER_DATASETS))
def test_paper_dataset_scales_as_documented(name):
    """``scale`` shrinks nnz by itself and every dim by its cube root, so the
    density holds, with floors of 64 non-zeros and 8 rows a mode; the order
    stays the published one."""
    dims, nnz, _ = PAPER_DATASETS[name]
    scale = 300 / nnz
    t = paper_dataset(name, KEY, scale=scale)
    assert t.dims == tuple(max(8, int(d * scale ** (1 / 3))) for d in dims)
    # duplicates drawn at the same coordinates are summed into one
    assert 0.9 * int(nnz * scale) <= t.nnz <= int(nnz * scale)
    inds = np.asarray(t.inds[:t.nnz])
    assert (inds >= 0).all() and (inds < np.array(t.dims)).all()
    floor = paper_dataset(name, KEY, scale=1e-15)
    assert floor.dims == (8,) * len(dims) and 0 < floor.nnz <= 64


@pytest.mark.parametrize("workload", sorted(PAPER_WORKLOADS))
def test_launcher_id_resolves_through_the_paper_table(workload):
    """A launcher id names one PAPER_DATASETS entry: the dry-run lowers its
    published dims and nnz at the paper's rank 35, and the serving
    launcher's RunConfig draws that data set."""
    from repro.core.distributed import build_dist_cpals_lowered
    from repro.dist.collectives import make_mesh
    from repro.launch.serve import cpd_config

    dataset = PAPER_WORKLOADS[workload]
    dims, nnz, _ = PAPER_DATASETS[dataset]
    mesh = make_mesh((1, 1), ("data", "model"))
    _, info = build_dist_cpals_lowered(workload, mesh)
    assert (info["dims"], info["nnz"], info["rank"]) == (dims, nnz, 35)
    assert PAPER_RANK == 35
    cfg = cpd_config(workload, smoke=False, rank=PAPER_RANK, niters=1,
                     policy="auto", seed=0, reorder="identity", cache=None,
                     method="cp_als")
    assert (cfg.data.dataset, cfg.data.scale) == (dataset, 1.0)


# each launcher and the flag that names its workload
FLAGS = {"launch.dryrun": "--arch", "launch.serve": "--arch",
         "repro-dryrun": "--workload"}


def run_entry(entry, args, monkeypatch, capsys):
    """(exit code, stdout, stderr) of one launcher's command line."""
    if entry == "launch.dryrun":
        # in a child: importing the module sets XLA_FLAGS for 512 host devices
        r = run_module(["repro.launch.dryrun", *args])
        return r.returncode, r.stdout, r.stderr
    if entry == "launch.serve":
        from repro.launch import serve

        monkeypatch.setattr(sys, "argv", ["serve", *args])
        main = serve.main
    else:
        from repro.api import cli

        def main():
            return cli.main(["dryrun", *args])
    try:
        rc = main() or 0
    except SystemExit as e:
        rc = e.code
    out = capsys.readouterr()
    return rc, out.out, out.err


@pytest.mark.parametrize("entry", list(FLAGS))
def test_launchers_refuse_unknown_ids_naming_the_known_ones(
        entry, monkeypatch, capsys):
    rc, _, err = run_entry(entry, [FLAGS[entry], "gemma-7b"], monkeypatch,
                           capsys)
    assert rc == 2, err
    assert "invalid choice: 'gemma-7b'" in err, err
    for workload in PAPER_WORKLOADS:
        assert workload in err, err


@pytest.mark.parametrize("entry", list(FLAGS))
def test_launchers_accept_the_paper_ids(entry, tmp_path, monkeypatch,
                                        capsys):
    """The spellings users type: ``--arch cpals-yelp`` on both launchers,
    whose dry-run artifact cell id is ``<id>__iteration__<mesh>``, and
    ``repro dryrun --workload cpals-yelp``, which hands it to the dry-run's
    own interpreter."""
    args = [FLAGS[entry], "cpals-yelp"]
    if entry == "launch.dryrun":
        args += ["--out", str(tmp_path)]
    elif entry == "launch.serve":
        args += ["--smoke", "--rank", "4", "--iters", "1", "--queries", "64",
                 "--batch", "32"]
    else:
        calls = []
        monkeypatch.setattr(subprocess, "call",
                            lambda cmd, env: calls.append((cmd, env)) or 0)
    rc, out, err = run_entry(entry, args, monkeypatch, capsys)
    assert rc == 0, (out, err[-4000:])
    if entry == "launch.dryrun":
        cell = "cpals-yelp__iteration__single"
        assert cell in out, out
        assert json.loads((tmp_path / f"{cell}.json").read_text())["cell"] \
            == cell
    elif entry == "launch.serve":
        assert "[serve] method cp_als" in out, out
    else:
        (cmd, env), = calls
        assert cmd[1:] == ["-m", "repro.launch.dryrun", "--arch",
                           "cpals-yelp", "--mesh", "single"], cmd
        assert env["JAX_PLATFORMS"] == "cpu"
