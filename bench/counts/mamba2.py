"""Sizes of a Mamba2 model (SSD layers, no attention, tied embeddings)
for the counts in ``bench/harness/counts.py``."""

from harness import counts


def dims(config: dict) -> counts.Dims:
    d, layers = config["d_model"], config["n_layer"]
    d_inner = config["expand"] * d
    heads, hd = d_inner // config["headdim"], config["headdim"]
    n, groups, d_conv = config["d_state"], config["ngroups"], config["d_conv"]
    conv_dim = d_inner + 2 * groups * n
    item = counts.item_bytes(config)
    return counts.Dims(
        d=d, layers=layers, vocab=config["padded_vocab_size"], item=item,
        # in_proj to z, x, B, C and dt; out_proj
        layer_matrix_params=d * (2 * d_inner + 2 * groups * n + heads)
        + d_inner * d,
        # norm, conv taps and bias, dt bias, A, D, gated norm
        layer_vector_params=d + d_conv * conv_dim + conv_dim + 3 * heads
        + d_inner,
        # depthwise conv, then the recurrence: decay the state, add the
        # outer product dt*x (x) B, read it out against C
        layer_flops_fixed=2.0 * d_conv * conv_dim + 5.0 * heads * hd * n,
        layer_flops_per_position=0.0,
        state_bytes_per_token=0,
        # conv window and SSM state
        fixed_state_bytes=layers * item * ((d_conv - 1) * conv_dim
                                           + heads * hd * n),
    )
