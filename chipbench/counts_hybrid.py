"""Operations and bytes of the sparse linear-attention hybrid that
``reference_hybrid.py`` computes (the ``counts`` of ``qwen3-next-80b-a3b``
and ``tiny-hybrid``): blocks that mix by the gated delta rule or, every
``full_attention_interval``-th, by gated softmax attention, each followed
by sparse experts with a shared one; an untied head with a bias.

Pure arithmetic on the configuration's sizes, no jax: the parent loads
this file (``modules.counts_of``). A product of (m, k) by (k, n) is
2*m*k*n operations. The configuration is one chip's share: ``num_experts``
counts the experts HELD, ``num_routed_experts`` the router's width, so a
token multiplies ``num_experts_per_tok * num_experts /
num_routed_experts`` held experts on average.

The delta rule is counted by its recurrence (decay the state, read it
with the key, add the outer product, read it with the query: 7 operations
an element of the state a token), which is the least any way of computing
it does: a chunked form does more, and its share of this count then reads
under 100 %.
"""


def layer_kinds(cfg):
    """"attention" or "delta_rule" for each layer held here."""
    every = cfg["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "delta_rule"
            for i in range(cfg["num_hidden_layers"])]


def dims(cfg):
    kinds = layer_kinds(cfg)
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        window=None, pattern=kinds,
        attention_layers=kinds.count("attention"),
        delta_layers=kinds.count("delta_rule"),
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        experts_held=cfg["num_experts"],
        experts_routed=cfg["num_routed_experts"],
        top_k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        shared_f=cfg["shared_expert_intermediate_size"])


def attention_matrix_params(cfg):
    """wq (query and gate), wk, wv, wo."""
    s = dims(cfg)
    q_d, kv_d = s["h"] * s["hd"], s["kv"] * s["hd"]
    return s["d"] * 2 * q_d + 2 * s["d"] * kv_d + q_d * s["d"]


def delta_matrix_params(cfg):
    """in_qkvz, in_ba, out_proj, and the convolution's taps (a
    multiply-add a tap a channel a token, like a matrix's entry)."""
    s = dims(cfg)
    kd, vd = s["key_heads"] * s["dk"], s["value_heads"] * s["dv"]
    return (s["d"] * (2 * kd + 2 * vd) + s["d"] * 2 * s["value_heads"]
            + vd * s["d"] + (2 * kd + vd) * cfg["linear_conv_kernel_dim"])


def expert_matrix_params(cfg):
    """One routed expert: gate, up and down."""
    s = dims(cfg)
    return 3 * s["d"] * s["f"]


def sparse_block_params(cfg):
    """Router, the experts held, the shared expert and its gate."""
    s = dims(cfg)
    return (s["d"] * s["experts_routed"]
            + s["experts_held"] * expert_matrix_params(cfg)
            + 3 * s["d"] * s["shared_f"] + s["d"])


def model_params(cfg):
    """Everything held here: the blocks with their norms and small
    leaves, embedding, head and its bias."""
    s = dims(cfg)
    attn = attention_matrix_params(cfg) + 2 * s["hd"]
    delta = delta_matrix_params(cfg) + 2 * s["value_heads"] + s["dv"]
    return (s["attention_layers"] * attn + s["delta_layers"] * delta
            + s["layers"] * (sparse_block_params(cfg) + 2 * s["d"])
            + 2 * s["vocab"] * s["d"] + s["vocab"])


def matmul_params(cfg):
    """Parameters that a token multiplies here, on average: the mixers,
    the router, the shared expert, its share of the held experts, the
    sliced head. The embedding is a lookup."""
    s = dims(cfg)
    routed = (s["top_k"] * s["experts_held"] / s["experts_routed"]
              * expert_matrix_params(cfg))
    per_layer = (s["d"] * s["experts_routed"] + 3 * s["d"] * s["shared_f"]
                 + s["d"] + routed)
    return (s["attention_layers"] * attention_matrix_params(cfg)
            + s["delta_layers"] * delta_matrix_params(cfg)
            + s["layers"] * per_layer + s["d"] * s["vocab"])


def delta_rule_flops_forward(cfg, tokens):
    """The recurrence of ONE delta-rule layer over ``tokens`` positions:
    7 operations an element of each value head's (dk, dv) state a token."""
    s = dims(cfg)
    return 7.0 * s["value_heads"] * s["dk"] * s["dv"] * tokens


def attention_flops_forward(cfg, queries, keys_per_query):
    """Over all layers: QK^T and PV of the softmax layers for ``queries``
    positions that each see ``keys_per_query`` keys, and the delta-rule
    layers' recurrence for those positions (it sees no keys: its cost a
    token is fixed)."""
    s = dims(cfg)
    return (4.0 * s["attention_layers"] * s["h"] * s["hd"] * queries
            * keys_per_query
            + s["delta_layers"] * delta_rule_flops_forward(cfg, queries))


def kv_bytes_per_token(cfg, dtype_bytes):
    """K and V of one position over the softmax layers held here; a
    delta-rule layer keeps a state a sequence and nothing a token."""
    s = dims(cfg)
    return 2 * s["kv"] * s["hd"] * dtype_bytes * s["attention_layers"]


# -- what the new readers need ------------------------------------------------

def delta_rule_flops(cfg, tokens):
    """Forward and backward recurrence of ONE layer over ``tokens``."""
    return 3.0 * delta_rule_flops_forward(cfg, tokens)


def delta_rule_bytes(cfg, tokens, dtype_bytes=2):
    """q, k, v, g, beta and o of ONE layer over ``tokens`` positions, read
    or written once forward, and a gradient as large once backward."""
    s = dims(cfg)
    values = (2 * s["key_heads"] * s["dk"] + 2 * s["value_heads"] * s["dv"]
              + 2 * s["value_heads"])
    return 2.0 * values * dtype_bytes * tokens


def expert_flops(cfg, assignments):
    """Forward and backward of the routed experts' three matrices over
    ``assignments`` held (token, expert) pairs."""
    return 3.0 * 2.0 * expert_matrix_params(cfg) * assignments


def expert_bytes(cfg, assignments, layer_steps, weight_bytes=4,
                 token_bytes=2):
    """The held experts' matrices of ``layer_steps`` (layers x steps) read
    once forward and once backward and their gradient written once, and
    each assignment's token row read and its result written forward, the
    row and the result's gradient read and the row's gradient written
    backward."""
    s = dims(cfg)
    weights = (3.0 * s["experts_held"] * expert_matrix_params(cfg)
               * weight_bytes * layer_steps)
    return weights + 5.0 * s["d"] * token_bytes * assignments
