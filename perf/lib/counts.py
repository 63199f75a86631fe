"""Operations and bytes that the mathematics needs, from shapes alone.

Independent of what implements a layer: a kernel that recomputes, gathers
more than it needs or runs in another precision is held against the same
count. An operation is one floating-point add or multiply (a multiply-add is
two).
"""


def _attn_dim(cfg):
    return cfg["n_head"] * cfg["head_dim"]


def gpt2_forward_ops_per_token(cfg, context):
    """Forward pass, one token that attends causally within ``context``
    positions (training at sequence length S: context = S, the mask halves the
    4*S*ad of full attention to 2*S*ad on average). The arithmetic of
    benchmarks/_common.model_flops, copied: per block 8*d*ad for q, k, v and
    the output projection, 4*mlp_ratio*d^2 for the MLP, 2*context*ad for
    causal attention, and 2*d*V for the head."""
    d, ad = cfg["n_embd"], _attn_dim(cfg)
    per_block = 8 * d * ad + 4 * cfg["mlp_ratio"] * d * d + 2 * context * ad
    return cfg["n_layer"] * per_block + 2 * d * cfg["vocab_size"]


def gpt2_train_ops_per_token(cfg, seq_len):
    """Forward and backward (3 x forward), recomputation not counted."""
    return 3.0 * gpt2_forward_ops_per_token(cfg, seq_len)


def gpt2_token_ops_at(cfg, position):
    """Forward pass of ONE token at ``position`` (0-based) attending to the
    position + 1 keys it sees: 4*(position+1)*ad for its scores and values."""
    d, ad = cfg["n_embd"], _attn_dim(cfg)
    per_block = (8 * d * ad + 4 * cfg["mlp_ratio"] * d * d
                 + 4 * (position + 1) * ad)
    return cfg["n_layer"] * per_block + 2 * d * cfg["vocab_size"]


def gpt2_sequence_ops(cfg, start, stop):
    """Forward operations of the tokens at positions start..stop-1."""
    d, ad = cfg["n_embd"], _attn_dim(cfg)
    n = stop - start
    flat = cfg["n_layer"] * (8 * d * ad + 4 * cfg["mlp_ratio"] * d * d) \
        + 2 * d * cfg["vocab_size"]
    keys = (stop * (stop + 1) - start * (start + 1)) // 2
    return n * flat + cfg["n_layer"] * 4 * ad * keys


def gpt2_param_count(cfg, positions=None):
    d, ad, v = cfg["n_embd"], _attn_dim(cfg), cfg["vocab_size"]
    f = cfg["mlp_ratio"] * d
    pos = cfg["n_positions"] if positions is None else positions
    block = 4 * d + 3 * d * ad + ad * d + d * f + f + f * d + d
    return v * d + pos * d + cfg["n_layer"] * block + 2 * d + d * v


def causal_attention_forward(batch, heads, seq, head_dim, in_bytes=2):
    """-> (operations, bytes) of causal attention's forward at these shapes:
    QK^T and PV over the lower triangle (S*(S+1)/2 pairs, 2*dh operations
    each, twice), reading q, k, v and writing o once."""
    pairs = seq * (seq + 1) // 2
    ops = batch * heads * pairs * head_dim * 2 * 2
    moved = batch * heads * seq * head_dim * in_bytes * 4
    return ops, moved


def causal_attention_backward(batch, heads, seq, head_dim, in_bytes=2):
    """Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q, four
    matrix products over the triangle, plus the recomputation of S = QK^T that
    any backward without a stored S x S matrix needs: five in all. Reads q, k,
    v, o, do and writes dq, dk, dv."""
    pairs = seq * (seq + 1) // 2
    ops = batch * heads * pairs * head_dim * 2 * 5
    moved = batch * heads * seq * head_dim * in_bytes * 8
    return ops, moved


def roofline_seconds(ops, moved, peaks, ops_key="bf16_flops"):
    """-> (least seconds, which of 'compute' and 'bandwidth' bounds it)."""
    t_ops = ops / peaks[ops_key]
    t_mem = moved / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "bandwidth")


def gpt2_decode_step_bytes(cfg, context_lengths, weight_bytes=4, kv_bytes=4):
    """Least bytes one decode step moves: every weight once (the token
    embedding is a gather of one row a sequence, the position table likewise,
    so they are left out; the head is read whole), and the live keys and
    values of the in-flight sequences at their real lengths."""
    d, ad, v = cfg["n_embd"], _attn_dim(cfg), cfg["vocab_size"]
    f = cfg["mlp_ratio"] * d
    block = 4 * d + 3 * d * ad + ad * d + d * f + f + f * d + d
    weights = (cfg["n_layer"] * block + 2 * d + d * v) * weight_bytes
    kv = sum(context_lengths) * cfg["n_layer"] * 2 * ad * kv_bytes
    return weights + kv


# ResNet-50 (He et al. 2015, table 1, 50-layer column) -----------------------

RESNET50_STAGES = ((3, 256), (4, 512), (6, 1024), (3, 2048))


def resnet50_forward_ops_per_image(image=224, classes=1000):
    """Multiply-adds of every convolution and the classifier, times two. For
    224^2 and 1000 classes this gives 8.2e9 (4.1e9 multiply-adds; the paper's
    table says 3.8e9 because it counts the stride-2 3x3 convolutions of the
    original placement, stride on the first 1x1)."""
    macs = 0
    hw = image // 2                       # stem, stride 2
    macs += hw * hw * 7 * 7 * 3 * 64
    hw = hw // 2                          # max pool
    cin = 64
    for si, (blocks, width) in enumerate(RESNET50_STAGES):
        mid = width // 4
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            macs += hw * hw * cin * mid                      # 1x1
            out = hw // stride
            macs += out * out * 3 * 3 * mid * mid            # 3x3, strided
            macs += out * out * mid * width                  # 1x1
            if bi == 0:
                macs += out * out * cin * width              # projection
            hw, cin = out, width
    macs += 2048 * classes
    return 2 * macs


def resnet50_train_ops_per_image(image=224, classes=1000):
    return 3.0 * resnet50_forward_ops_per_image(image, classes)
