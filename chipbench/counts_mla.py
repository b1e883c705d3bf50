"""Operations and bytes of the latent-attention sparse decoder that
``reference_mla.py`` computes (the ``counts`` of ``moonlight-16b-a3b`` and
``tiny-mla``): blocks that mix by latent attention (MLA, trained
decompressed: per-head keys of ``qk_nope + qk_rope`` over values of
``v_head_dim``), the first ``first_k_dense_replace`` with a dense SwiGLU
and the others with sigmoid-routed experts beside an ungated shared
SwiGLU; a final norm and an untied head with a bias.

Pure arithmetic on the configuration's sizes, no jax: the parent loads
this file (``modules.counts_of``). A product of (m, k) by (k, n) is
2*m*k*n operations. The configuration is one chip's share:
``n_routed_experts`` counts the experts HELD, ``router_width`` the
router's, so a token multiplies ``num_experts_per_tok * n_routed_experts
/ router_width`` held experts on average, at uniform routing.

Attention's products are counted at the widths they have: QK^T at the key
width ``dk`` and PV at the value width ``dv``, not one head size for both.
"""


def dims(cfg):
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"], hd=dk, dk=dk,
        dv=cfg["v_head_dim"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], rank=cfg["kv_lora_rank"],
        layers=layers, vocab=cfg["vocab_size"], window=None,
        dense_layers=dense, moe_layers=layers - dense,
        experts_held=cfg["n_routed_experts"],
        experts_routed=cfg["router_width"],
        top_k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        shared_f=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        dense_f=cfg["intermediate_size"])


def latent_matrix_params(cfg):
    """W_q, W_kva, W_kvb and W_o of one layer."""
    s = dims(cfg)
    return (s["d"] * s["h"] * s["dk"] + s["d"] * (s["rank"] + s["rope"])
            + s["rank"] * s["h"] * (s["nope"] + s["dv"])
            + s["h"] * s["dv"] * s["d"])


def expert_matrix_params(cfg):
    """One routed expert: gate, up and down."""
    s = dims(cfg)
    return 3 * s["d"] * s["f"]


def shared_matrix_params(cfg):
    """The shared experts, one SwiGLU of their summed width."""
    s = dims(cfg)
    return 3 * s["d"] * s["shared_f"]


def dense_matrix_params(cfg):
    s = dims(cfg)
    return 3 * s["d"] * s["dense_f"]


def router_params(cfg):
    """The router and its selection's correction."""
    s = dims(cfg)
    return s["d"] * s["experts_routed"] + s["experts_routed"]


def dense_layer_params(cfg):
    """Latent attention, its latent's norm, the dense SwiGLU, two norms."""
    s = dims(cfg)
    return (latent_matrix_params(cfg) + s["rank"]
            + dense_matrix_params(cfg) + 2 * s["d"])


def expert_layer_params(cfg):
    """Latent attention, its latent's norm, the router, the experts held,
    the shared ones, two norms."""
    s = dims(cfg)
    return (latent_matrix_params(cfg) + s["rank"] + router_params(cfg)
            + s["experts_held"] * expert_matrix_params(cfg)
            + shared_matrix_params(cfg) + 2 * s["d"])


def vocab_params(cfg):
    """The embedding, the head and its bias: the vocabulary's slice."""
    s = dims(cfg)
    return 2 * s["vocab"] * s["d"] + s["vocab"]


def model_params(cfg):
    """Everything held here: the blocks, embedding, final norm, head."""
    s = dims(cfg)
    return (s["dense_layers"] * dense_layer_params(cfg)
            + s["moe_layers"] * expert_layer_params(cfg)
            + vocab_params(cfg) + s["d"])


def matmul_params(cfg):
    """Parameters that a token multiplies here, on average: latent
    attention's four matrices, the dense SwiGLU or the router, the shared
    experts and the token's share of the held experts, the sliced head.
    The embedding is a lookup."""
    s = dims(cfg)
    routed = (s["top_k"] * s["experts_held"] / s["experts_routed"]
              * expert_matrix_params(cfg))
    sparse = (s["d"] * s["experts_routed"] + shared_matrix_params(cfg)
              + routed)
    return (s["layers"] * latent_matrix_params(cfg)
            + s["dense_layers"] * dense_matrix_params(cfg)
            + s["moe_layers"] * sparse + s["d"] * s["vocab"])


def attention_flops_forward(cfg, queries, keys_per_query):
    """QK^T at the key width and PV at the value width over all layers,
    for ``queries`` positions that each see ``keys_per_query`` keys."""
    s = dims(cfg)
    return (2.0 * s["layers"] * s["h"] * queries * keys_per_query
            * (s["dk"] + s["dv"]))


def kv_bytes_per_token(cfg, dtype_bytes):
    """What a latent cache would hold of one position over the layers:
    the latent and the rope key, ``kv_lora_rank + qk_rope_head_dim``."""
    s = dims(cfg)
    return (s["rank"] + s["rope"]) * dtype_bytes * s["layers"]


# -- what the new readers need ------------------------------------------------

def flash_flops(cfg, seq_len, sequences):
    """Forward and backward of ONE layer's causal attention over
    ``sequences`` of ``seq_len``: QK^T (dk) and PV (dv) forward; dV and dP
    (dv), dQ and dK (dk) backward, each over the keys a query sees. The
    recomputed QK^T is not counted."""
    s = dims(cfg)
    ctx = (seq_len + 1) / 2.0
    product = 2.0 * sequences * s["h"] * seq_len * ctx
    return product * (3 * s["dk"] + 3 * s["dv"])


def flash_bytes(cfg, seq_len, sequences, dtype_bytes=2):
    """q, k, v, o, dO, dQ, dK and dV of ONE layer at their widths (q, k,
    dQ, dK at dk; v, o, dO, dV at dv), each read or written once."""
    s = dims(cfg)
    return (4.0 * (s["dk"] + s["dv"]) * s["h"] * seq_len * sequences
            * dtype_bytes)


def expert_flops(cfg, assignments):
    """Forward and backward of the routed experts' three matrices over
    ``assignments`` held (token, expert) pairs."""
    return 3.0 * 2.0 * expert_matrix_params(cfg) * assignments


def expert_bytes(cfg, assignments, layer_steps, weight_bytes=4,
                 token_bytes=2):
    """The held experts' matrices of ``layer_steps`` (expert layers x
    steps) read once forward and once backward and their gradient written
    once, and each assignment's token row read and its result written
    forward, the row and the result's gradient read and the row's
    gradient written backward."""
    s = dims(cfg)
    weights = (3.0 * s["experts_held"] * expert_matrix_params(cfg)
               * weight_bytes * layer_steps)
    return weights + 5.0 * s["d"] * token_bytes * assignments
