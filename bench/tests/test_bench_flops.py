"""Operation and byte counts against hand sums at one shape."""
from bench import flops

MC = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
      "num_key_value_heads": 2, "intermediate_size": 16, "vocab_size": 10,
      "tie_word_embeddings": False, "model_type": "qwen2"}


def test_matmul_params():
    # q 8x8, k,v 8x4 each, o 8x8, gate/up/down 8x16 x3
    assert flops.matmul_params(MC) == 64 + 32 + 32 + 64 + 3 * 128


def test_decode_flops():
    # two rows at positions 0 and 3: 1 and 4 keys
    mm = 2 * 2 * 576 * 2                     # 2 flops x L x params x rows
    attn = 4 * 2 * 4 * 2 * (1 + 4)           # 4 x L x H x hd x keys
    head = 2 * 8 * 10 * 2                    # 2 x d x V x rows
    assert flops.decode_flops(MC, [0, 3]) == mm + attn + head


def test_chunk_flops_and_cost():
    # 3 tokens from position 2: 3, 4, 5 keys; one row of logits
    f = flops.chunk_flops(MC, 2, 3)
    assert f == 2 * 2 * 576 * 3 + 4 * 2 * 4 * 2 * 12 + 2 * 8 * 10
    af, ab = flops.chunk_attn_cost(MC, 2, 3, 2)
    assert af == 4 * 2 * 4 * 2 * 12
    # per layer: K and V of 5 positions x 2 heads x hd 2 x 2 bytes,
    # plus 3 queries of 4 heads x hd 2 in (2 B) and out (4 B)
    assert ab == 2 * (2 * 2 * 2 * 2 * 5 + 3 * 4 * 2 * 6)


def test_decode_attn_cost():
    f, b = flops.decode_attn_cost(MC, [0, 3], 2)
    assert f == 4 * 2 * 4 * 2 * 5
    assert b == 2 * (2 * 2 * 2 * 2 * 5 + 2 * 4 * 2 * 6)


def test_weight_and_kv_bytes():
    assert flops.kv_bytes_per_token(MC, 2) == 2 * 2 * 2 * 2 * 2
    biases = (4 + 2 * 2) * 2
    per_layer = (576 + biases) * 2 + 2 * 8 * 4
    assert flops.weight_bytes(MC, 2) == 2 * per_layer + 10 * 8 * 2 + 8 * 4
