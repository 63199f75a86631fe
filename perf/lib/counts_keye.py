"""Operations and bytes of the Keye-VL-2.0 language model's serving step, from
the configuration's shapes, the sequences' contexts and the program's
counters (how many experts got a token): what the mathematics needs, whatever
implements it. A program that gathers more index keys than are live, masks a
dense walk where a gather would do, or reads an expert twice is held against
the same count. An operation is one floating-point add or multiply.
"""

BF16 = 2


def dims(cfg):
    sa = cfg["sa_config"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "g": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "j": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
            "topk": sa["topk"], "e": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def dense_params_per_layer(cfg):
    """Attention projections, the indexer's three and the router: what every
    token multiplies whatever it is routed to."""
    s = dims(cfg)
    attn = s["d"] * s["h"] * s["dh"] * 2 + s["d"] * 2 * s["g"] * s["dh"]
    indexer = s["d"] * (s["j"] * s["di"] + s["di"] + s["j"])
    return attn + indexer + s["d"] * s["e"]


def expert_params(cfg):
    """One expert: gate, up and down."""
    s = dims(cfg)
    return 3 * s["d"] * s["f"]


def param_count(cfg):
    s = dims(cfg)
    layer = dense_params_per_layer(cfg) + s["e"] * expert_params(cfg)
    return s["layers"] * layer + 2 * s["v"] * s["d"]


def token_flat_ops(cfg, head=True):
    """Forward operations of one token that do not depend on its context:
    the dense matrices, its ``k`` experts, and the head where its logits are
    produced (a decoded token and a prompt's last; the rest of a prompt
    stops at the last layer)."""
    s = dims(cfg)
    layer = 2 * dense_params_per_layer(cfg) + 2 * s["k"] * expert_params(cfg)
    return s["layers"] * layer + (2 * s["d"] * s["v"] if head else 0)


def index_ops(cfg, context_positions):
    """Index scores of all layers (``context_positions`` = sum over queries
    of the positions each may read): ``index_select``'s operations a layer."""
    return dims(cfg)["layers"] * index_select(cfg, context_positions)[0]


def selected_attention_ops(cfg, selected_positions):
    """Attention over the selected rows, all layers (``selected_positions``
    = sum over queries of min(topk, context)): ``selected_rows``'s
    operations a layer."""
    return dims(cfg)["layers"] * selected_rows(cfg, selected_positions)[0]


def span_sums(start, stop, topk):
    """For the queries at positions start..stop-1, each reading itself and
    before: (sum of contexts, sum of min(topk, context))."""
    def tri(n):
        return n * (n + 1) // 2

    contexts = tri(stop) - tri(start)
    cut = min(max(start, min(stop, topk)), stop)   # positions < cut: all kept
    selected = tri(cut) - tri(start) + (stop - cut) * topk
    return contexts, selected


def prefill_ops(cfg, start, stop, last):
    """Forward operations of a prompt's tokens at positions start..stop-1;
    ``last``: the chunk ends the prompt, so one token's logits are made."""
    s = dims(cfg)
    contexts, selected = span_sums(start, stop, s["topk"])
    head = 2 * s["d"] * s["v"] if last else 0
    return ((stop - start) * token_flat_ops(cfg, head=False) + head
            + index_ops(cfg, contexts) + selected_attention_ops(cfg, selected))


def decode_ops(cfg, tokens, context_positions, selected_positions):
    """Forward operations of one decode step: ``tokens`` sequences, their
    contexts and selected positions summed."""
    return (tokens * token_flat_ops(cfg) + index_ops(cfg, context_positions)
            + selected_attention_ops(cfg, selected_positions))


def expert_walk(cfg, experts_hit, pairs):
    """-> (operations, bytes) of the grouped products of ``pairs``
    token-expert pairs over ``experts_hit`` distinct experts: each hit
    expert's weights read once; activations are small beside them."""
    return 2 * pairs * expert_params(cfg), experts_hit * expert_params(cfg) * BF16


def index_select(cfg, context_positions):
    """-> (operations, bytes) of scoring ``context_positions`` index keys,
    each read once, one layer."""
    s = dims(cfg)
    return (context_positions * s["j"] * s["di"] * 2,
            context_positions * s["di"] * BF16)


def selected_rows(cfg, selected_positions):
    """-> (operations, bytes) of attention over ``selected_positions``
    selected rows of K and V, each read once, one layer."""
    s = dims(cfg)
    return (selected_positions * s["h"] * s["dh"] * 2 * 2,
            selected_positions * 2 * s["g"] * s["dh"] * BF16)


def decode_step_bytes(cfg, tokens, context_positions, selected_positions,
                      experts_hit):
    """Least bytes one decode step moves: the dense weights and the head
    once, the experts that got a token (``experts_hit`` summed over layers),
    the live index keys and the selected rows of K and V of every layer. The
    embedding is a gather of one row a sequence and is left out."""
    s = dims(cfg)
    dense = (s["layers"] * dense_params_per_layer(cfg) + s["d"] * s["v"]) * BF16
    experts = experts_hit * expert_params(cfg) * BF16
    keys = s["layers"] * index_select(cfg, context_positions)[1]
    rows = s["layers"] * selected_rows(cfg, selected_positions)[1]
    return dense + experts + keys + rows


def roofline(ops, moved, peaks):
    """Least seconds: the larger of operations over the bf16 peak and bytes
    over the HBM rate."""
    return max(ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
