"""The served path against the plain reference at an odd query group
(Qwen2.5-7B-Instruct puts 7 query heads over each KV head): a small
Qwen2 shape in float32 on the CPU, chunked prefill and then paged decode
through ``PagedScheduler`` with the Pallas kernels in interpret mode,
compared at every served position with ``bench/reference.py``'s full
forward over the same seeded weights. The same run in bfloat16 has to
miss the float32 tolerance: the comparison tells the precisions apart."""
import jax
import numpy as np
import pytest

from bench import model, reference
from repro.configs.base import KappaConfig
from repro.models import attention

SEED = 2 ** 34 + 15
HEAD_DIM = 16
PROMPT, MAX_NEW, K = 21, 14, 5
# serving: 8-token pages and chunks, so the prompt fills three chunks
# (8, 8, 5) and decode crosses two page boundaries
SERVING = {"page_size": 8, "num_pages": 24, "rows": 5, "max_seq": 40,
           "prefill_chunk": 8}
# float32 program against the float32 reference at HIGHEST: the two sum
# in different orders (kernel tiles, online softmax), a few float32 ulps
# on logits below 1 (about 5e-7 here); 1e-4 leaves two orders of
# magnitude of room, and bfloat16's rounding of the same logits (2**-8
# relative) misses it twentyfold (about 5e-3 here)
RTOL = ATOL = 1e-4


def _mc(heads: int, kv_heads: int, dtype: str) -> dict:
    return {"model_type": "qwen2", "name": f"toy-g{heads // kv_heads}",
            "source": "toy", "hidden_size": heads * HEAD_DIM,
            "intermediate_size": 128, "num_attention_heads": heads,
            "num_key_value_heads": kv_heads, "num_hidden_layers": 2,
            "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
            "tie_word_embeddings": False, "torch_dtype": dtype,
            "use_sliding_window": False, "vocab_size": 512,
            "bos_token_id": 1, "eos_token_id": 2}


def _serve(mc: dict):
    """Serve one greedy request; returns its prompt and served tokens
    and the program's logits by position (the final prefill chunk's,
    then every decode step's), with which backend each attention took."""
    cfg = model.model_config(mc)
    params = model.make_params(cfg, mc, SEED)
    logits = {}
    attention.reset_paged_backend_counts()
    attention.set_paged_kernel(True)
    try:
        with jax.default_matmul_precision("highest"):
            sched = model.make_scheduler(params, cfg, mc,
                                         KappaConfig(max_new_tokens=MAX_NEW),
                                         SERVING)
            orig_start, orig_decode = sched._start_request, sched._decode_tick

            def start(item, slots, pf_logits):
                logits[len(item.prompt) - 1] = np.asarray(pf_logits)
                return orig_start(item, slots, pf_logits)

            def decode_tick():
                out = orig_decode()
                for _, slots in sched.active.values():
                    s = slots[0]
                    logits[int(sched.row_pos[s])] = np.asarray(out[s])
                return out

            sched._start_request, sched._decode_tick = start, decode_tick
            prompt = np.random.default_rng(3).integers(
                3, mc["vocab_size"], PROMPT, dtype=np.int32)
            rid = sched.submit(prompt, jax.random.PRNGKey(0), max_new=MAX_NEW,
                               method="greedy")
            res = sched.run()[rid]
    finally:
        attention.set_paged_kernel(None)
    return prompt, np.asarray(res.tokens, np.int32), logits, \
        attention.paged_backend_counts()


def _deviation(mc: dict):
    """Largest |program - reference| over the best, k-th best and served
    logits at every served position, beside the tolerance there."""
    prompt, toks, logits, counts = _serve(mc)
    assert counts["decode_kernel"] >= 1 and counts["prefill_kernel"] >= 1
    assert counts["decode_oracle"] == 0 and counts["prefill_oracle"] == 0, \
        counts
    seq = np.concatenate([prompt, toks])
    [ref] = reference.forward(SEED, mc, [seq], k=K)
    pos = np.arange(PROMPT - 1, len(seq) - 1)
    assert len(toks) == MAX_NEW and sorted(logits) == list(pos)
    got = np.stack([logits[p] for p in pos])
    prog = {"best": got.max(-1), "kth": np.sort(got, -1)[:, -K],
            "served": got[np.arange(len(pos)), seq[pos + 1]]}
    worst = 0.0
    for f, v in prog.items():
        want = ref[f][pos]
        excess = np.abs(v - want) - (ATOL + RTOL * np.abs(want))
        worst = max(worst, float(excess.max()))
    return worst


@pytest.mark.parametrize("heads,kv_heads", [(7, 1), (14, 2)],
                         ids=["7-over-1", "14-over-2"])
def test_paged_serving_matches_reference_at_odd_group(heads, kv_heads):
    assert _deviation(_mc(heads, kv_heads, "float32")) <= 0.0


def test_bfloat16_serving_misses_the_float32_tolerance():
    assert _deviation(_mc(7, 1, "bfloat16")) > 0.0
