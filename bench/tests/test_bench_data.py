"""The benchmark's data files against each other and the program: every
configuration builds the program's ``ModelConfig`` at the widths of the
program's own config of the same published model (a cut may change
depth only), every cell's pre-roll can be admitted at its rows, and its
limits name numbers that the reference comparison reports."""
import json
from pathlib import Path

import pytest

from bench import harness, model, reference
from repro.configs import all_configs
from repro.serving import strategies

BENCH = harness.Bench()
WIDTHS = ("d_model", "num_heads", "num_kv_heads", "resolved_head_dim",
          "d_ff", "vocab_size", "qkv_bias", "rope_theta", "norm_eps",
          "layer_pattern")


def _hf(url: str) -> str:
    return "hf:" + url.split("huggingface.co/", 1)[1].strip("/")


@pytest.mark.parametrize("entry", BENCH.spec["configs"],
                         ids=lambda c: c["name"])
def test_config_builds_at_published_widths(entry):
    mc = BENCH.config(entry["name"])
    cfg = model.model_config(mc)
    [own] = [c for c in all_configs().values()
             if c.source == _hf(entry["source"])]
    for w in WIDTHS:
        assert getattr(cfg, w) == getattr(own, w), w
    assert cfg.dtype == mc["torch_dtype"] == own.dtype
    reduced = set(entry["reduced"])
    assert reduced <= set(mc), reduced - set(mc)
    if "num_hidden_layers" in reduced:
        assert cfg.num_layers < own.num_layers
    else:
        assert cfg.num_layers == own.num_layers
    # the file names each cut with its published value
    assert [r.split(":")[0] for r in mc["reduced"]] == entry["reduced"]


@pytest.mark.parametrize("cell", BENCH.spec["workloads"],
                         ids=lambda c: c["name"])
def test_preroll_fits_the_rows(cell):
    """The pre-roll admits a group once its fan-out fits the free rows,
    and waits for every group before it to settle: with all earlier
    requests decided onto one row each, the last group still fits."""
    serving = BENCH.config(cell["config"])["serving"]
    tr = BENCH.traffic(cell["traffic"])
    pre = tr["preroll"]
    group = pre["group"]
    n = strategies.make_strategy(tr["method"]).rows(model.kappa_config(tr))
    requests = pre["requests"] // group * group
    assert requests == pre["requests"]
    assert requests - group + group * n <= serving["rows"]
    assert pre["prefill_chunk"] == serving["prefill_chunk"]


@pytest.mark.parametrize("cell", BENCH.spec["workloads"],
                         ids=lambda c: c["name"])
def test_limits_name_compared_numbers(cell):
    """Every cell has a limit, and each limit names a number the
    reference comparison reports (``correct`` reads it by that name)."""
    limits = json.loads(Path(BENCH.dir / "limits" / f"{cell['name']}.json")
                        .read_text())
    assert limits
    assert set(limits) <= set(reference.gaps([], [])[0])
    assert all(v > 0 for v in limits.values())
