"""Sizes of a Qwen3 model (dense GQA attention with qk-norm, SwiGLU,
tied embeddings) for the counts in ``bench/harness/counts.py``."""

from harness import counts


def dims(config: dict) -> counts.Dims:
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    item = counts.item_bytes(config)
    return counts.Dims(
        d=d, layers=layers, vocab=config["vocab_size"], item=item,
        # q, k, v, o projections; gate, up and down
        layer_matrix_params=(d * hd * (2 * heads + 2 * kv)
                             + 3 * d * config["intermediate_size"]),
        # input and post-attention norms; q and k norms
        layer_vector_params=2 * d + 2 * hd,
        layer_flops_fixed=0.0,
        # scores and weighted values over the positions read
        layer_flops_per_position=4.0 * heads * hd,
        state_bytes_per_token=2 * layers * kv * hd * item,
        fixed_state_bytes=0,
    )
