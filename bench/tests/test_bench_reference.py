"""The plain reference against the program at a toy size: the same
named weights from the seed give the same logits, and the comparison
numbers read zero for tokens drawn from the model's own top k and
clearly above for a token outside it."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import model, reference

TOY = json.loads((Path(__file__).parent / "data/toy.json").read_text())


def _toy(dtype):
    mc = dict(TOY, torch_dtype=dtype, name="toy", source="toy")
    return mc, model.model_config(mc)


def test_reference_matches_program_logits():
    from repro.models import train_logits
    mc, cfg = _toy("float32")
    seed = 2 ** 35 + 3
    params = model.make_params(cfg, mc, seed)
    toks = np.random.default_rng(0).integers(0, mc["vocab_size"], 40,
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = train_logits(params, cfg, jnp.asarray(toks)[None])
    want = np.asarray(want[0])
    [got] = reference.forward(seed, mc, [toks], k=5)
    np.testing.assert_allclose(got["best"], want.max(-1), rtol=1e-4,
                               atol=1e-4)
    # position i's served token is token i + 1 (the last has none)
    np.testing.assert_allclose(got["served"][:-1],
                               want[np.arange(len(toks) - 1), toks[1:]],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got["kth"], np.sort(want, -1)[:, -5],
                               rtol=1e-4, atol=1e-4)


def test_gaps_read_what_was_served():
    mc, _ = _toy("bfloat16")
    toks = np.random.default_rng(1).integers(0, mc["vocab_size"], 24,
                                             dtype=np.int32)
    rule = (0.7, 5, 0.95)
    # a continuation from position 8 on, each token drawn by the
    # sampling rule from the reference's own logits: every gap is 0
    seq = toks.copy()
    for i in range(8, len(seq)):
        [r] = reference.forward(7, mc, [seq], k=5, sample=rule)
        seq[i] = r["sampled"][i - 1]
    [r] = reference.forward(7, mc, [seq], k=5)
    nums, n = reference.gaps([r], [8])
    assert n == 16 and nums["kappa_topk_gap"] == 0.0
    assert nums["kappa_topk_miss"] == 0.0
    # the same tokens, one replaced by a token outside the top 5
    bad = seq.copy()
    bad[12] = (seq[12] + 1) % mc["vocab_size"]
    [rb] = reference.forward(7, mc, [bad], k=5)
    nums, n = reference.gaps([rb], [8])
    assert n == 16 and nums["kappa_topk_gap"] > 0.0
    assert nums["kappa_topk_gap_mean"] > 0.0
    # the altered token, and any later one its context moved out
    assert nums["kappa_topk_miss"] >= 100.0 / 16


def test_control_picks_are_read_at_the_same_positions():
    """The int8 control's own draws, read under the float32 reference:
    its picks land at the positions served, and a pick the reference
    ranks inside its top k reads 0."""
    mc, _ = _toy("bfloat16")
    seq = np.random.default_rng(2).integers(0, mc["vocab_size"], 40,
                                            dtype=np.int32)
    rule = (0.7, 5, 0.95)
    [low] = reference.forward(7, mc, [seq], k=5, quant=True, sample=rule)
    assert low["sampled"].shape == (40,)
    [r] = reference.forward(7, mc, [seq], k=5, read=[low["sampled"]])
    nums, n = reference.gaps([r], [8], field="read")
    assert n == 32
    inside = r["read"][7:39] >= r["kth"][7:39]
    assert (nums["kappa_topk_miss"] == 0.0) == bool(inside.all())
