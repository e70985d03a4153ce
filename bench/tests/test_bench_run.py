"""The harness end to end at a toy size on the CPU, in a copy of the
benchmark's files to which a toy configuration, two toy traffic mixes
and their cells were added by name only: it finds them, serves through
the program's front-end, reports the cell's metrics and decides
``correct`` against the reference; with a served token altered where
it is produced, ``correct`` comes out false. And ``bench/run.py`` off
a TPU exits non-zero with no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import toy

REPO = Path(__file__).resolve().parents[2]
SEED = 2 ** 33 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(tmp_path_factory.mktemp("bench"), limits=0.05)


def test_run_exits_nonzero_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "r1d-1.5b.kappa-batch", "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


def test_toy_batch_cell_is_found_and_correct(root):
    run = harness.run_cell("toy.batch", SEED, 2.0, False, root=root,
                           require_tpu=False)
    res = run.result
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "answer_tok_s"}
    assert res["metrics"]["answer_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["tokens"] > 0 for c in run.checks.values())
    json.dumps(res)


def test_toy_poisson_cell_reports_its_metrics(root):
    run = harness.run_cell("toy.poisson", SEED + 1, 2.0, False, root=root,
                           require_tpu=False)
    res = run.result
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_s", "itl_p50_s",
                                   "itl_p95_s"}
    assert res["metrics"]["ttft_p90_s"]["value"] > 0


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.serving import sampler
    orig = sampler.sample_rows

    def altered(keys, logits, greedy_mask, kcfg, **kw):
        out = orig(keys, logits, greedy_mask, kcfg, **kw)
        toks = out[0] if isinstance(out, tuple) else out
        toks = (toks + 1) % logits.shape[-1]
        return (toks,) + tuple(out[1:]) if isinstance(out, tuple) else toks

    monkeypatch.setattr(sampler, "sample_rows", altered)
    run = harness.run_cell("toy.batch", SEED, 2.0, False, root=root,
                           require_tpu=False)
    assert not run.result["correct"]
    assert run.checks["kappa_topk_gap"]["value"] > 0.05


def test_control_fails_where_the_program_passes(tmp_path):
    """The control (the reference one precision lower, int8, in the
    program's place, sampling by the mix's rule) at the toy size, whose
    program serves in float32: the program reads inside the toy cell's
    limit, and in control mode the run reports not correct."""
    root = toy.make_root(tmp_path, limits=0.05)
    (root / "bench/limits/toy.batch.json").write_text(json.dumps(
        {"kappa_topk_gap": 2e-4}))
    run = harness.run_cell("toy.batch", SEED, 2.0, False, root=root,
                           require_tpu=False)
    assert run.result["correct"], run.checks
    run = harness.run_cell("toy.batch", SEED, 2.0, False, root=root,
                           require_tpu=False, control=True)
    assert not run.result["correct"], run.checks
    c = run.checks["kappa_topk_gap"]
    assert c["value"] <= c["limit"] < c["control"]
    assert run.result["checks"]["kappa_topk_gap"]["value"] == c["control"]
