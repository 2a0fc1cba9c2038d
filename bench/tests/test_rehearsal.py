"""Every cell end to end at a tiny size on the CPU, through ``run.main``
with the look for an accelerator skipped."""
import json
import shutil
import textwrap

import pytest

from bench import run
from bench.tests import tiny

CELLS = ["paper2d.apply_r64", "paper2d.build", "paper2d.serve_apply"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def run_cell(root, cell, capsys, seed=2 ** 31 + 12345, seconds=1.0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], root=root,
                  require_accelerator=False)
    out = capsys.readouterr()
    return rc, out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(root, cell, capsys):
    path, names = root
    rc, out = run_cell(path, names[cell], capsys)
    assert rc == 0, out.err[-3000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] >= 1
    with open(f"{path}/BENCHMARK.json") as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or names[cell] in m["workloads"]}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # the compared numbers, each beside its limit, end standard error
    last = out.err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in last)


def test_no_accelerator_prints_no_result(root, capsys):
    path, names = root
    rc = run.main(["--workload", names[CELLS[0]], "--seed", "1",
                   "--seconds", "1"], root=path)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""


def _add_cell(path, cell, config, traffic, like, end_to_end):
    """Entries in BENCHMARK.json for a new cell, and its limits as those
    of the cell ``like``."""
    bench_json = f"{path}/BENCHMARK.json"
    with open(bench_json) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "a later cell"})
    for m in bench["end_to_end"]:
        if m["name"] == end_to_end:
            m["workloads"].append(cell)
    with open(bench_json, "w") as f:
        json.dump(bench, f)
    shutil.copy(f"{path}/bench/limits/{like}.json",
                f"{path}/bench/limits/{cell}.json")


def _write(path, rel, text):
    with open(f"{path}/bench/{rel}", "w") as f:
        f.write(text)


def test_new_cell_from_files_alone(root, capsys):
    """A later cell with its own loop and its own arrival process: new
    files and new entries in BENCHMARK.json; no file of the harness
    changes."""
    path, names = root
    # a loop of its own, found by the name its traffic gives
    _write(path, "loops/apply_vec.py", textwrap.dedent("""
        from bench import common, suite

        control = common.apply_control


        class Loop(suite.loop("apply").Loop):
            \"\"\"Products of single vectors, each waited on.\"\"\"
        """))
    with open(f"{path}/bench/traffic/apply_r64.json") as f:
        traffic = json.load(f)
    _write(path, "traffic/apply_vec.json",
           json.dumps(dict(traffic, loop="apply_vec", cols=1)))
    cell = "tiny_paper2d.apply_vec"
    _add_cell(path, cell, "tiny_paper2d", "apply_vec",
              names["paper2d.apply_r64"], "apply_cols_per_s")
    rc, out = run_cell(path, cell, capsys, seed=7)
    assert rc == 0, out.err[-3000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"apply_cols_per_s", "setup_s"}

    # an arrival process of its own: evenly spaced requests
    _write(path, "arrivals/even.py", textwrap.dedent("""
        import numpy as np

        def gaps(traffic, seconds, seed):
            rate = traffic["rate_per_s"]
            return np.full(max(1, int(round(rate * seconds))), 1.0 / rate)
        """))
    with open(f"{path}/bench/traffic/serve_apply.json") as f:
        traffic = json.load(f)
    _write(path, "traffic/serve_even.json",
           json.dumps(dict(traffic, arrivals="even", rate_per_s=40)))
    cell = "tiny_paper2d.serve_even"
    _add_cell(path, cell, "tiny_paper2d", "serve_even",
              names["paper2d.serve_apply"], "serve_p95_ms")
    rc, out = run_cell(path, cell, capsys, seed=8)
    assert rc == 0, out.err[-3000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True and res["attempted"] == 40


def test_reference_kernel_from_a_file(root):
    """A configuration's kernel is found by its name in bench/kernels/."""
    import jax.numpy as jnp
    import numpy as np

    from bench import reference, suite
    path, _ = root
    _write(path, "kernels/laplace_l1.py", textwrap.dedent("""
        import jax.numpy as jnp

        def kernel(y, yp):
            return jnp.exp(-jnp.abs(y[:, None, :] - yp[None, :, :]).sum(-1))
        """))
    k = suite.kernel("laplace_l1", path)
    pts = suite.points("halton", path)({"n_points": 300, "dim": 2,
                                         "side": 1.0})
    x = jnp.ones((300, 2), jnp.float32)
    z = reference.dense_apply(pts, x, kernel=k, block=128)
    p = np.asarray(pts, np.float64)
    want = np.exp(-np.abs(p[:, None] - p[None]).sum(-1)) @ np.ones((300, 2))
    assert np.allclose(np.asarray(z), want, rtol=1e-5)
