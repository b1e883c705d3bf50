"""Operations and bytes of the window/full sparse decoder that
``reference_mimo.py`` computes (the ``counts`` of ``mimo-v2.5`` and
``tiny-mimo``): blocks whose attention is full or bounded by a window, at
head counts that differ by the kind, with keys wider than values; a dense
SwiGLU or sigmoid-routed sparse experts without a shared one; an untied
head with a bias.

Pure arithmetic on the configuration's sizes, no jax: the parent loads
this file (``modules.counts_of``). A product of (m, k) by (k, n) is
2*m*k*n operations. The configuration is one chip's share:
``n_routed_experts`` counts the experts HELD, ``router_width`` the
router's, so a token multiplies ``num_experts_per_tok * n_routed_experts /
router_width`` held experts on average, **at uniform routing**: the seed's
router is a random matrix and nothing trains it here.

``dims`` gives ``window: None``: the window is some layers', not the
model's, so ``work.token_flops`` hands this module every query's whole
context and ``attention_flops_forward`` clips the window layers' keys
itself.
"""


def layer_kinds(cfg):
    """[("full" | "window", "dense" | "experts")] of the layers held."""
    n = cfg["num_hidden_layers"]
    return [("window" if w else "full", "experts" if e else "dense")
            for w, e in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def dims(cfg):
    kinds = layer_kinds(cfg)
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], window=None, pattern=kinds,
        full_layers=sum(1 for a, _ in kinds if a == "full"),
        window_layers=sum(1 for a, _ in kinds if a == "window"),
        expert_layers=sum(1 for _, f in kinds if f == "experts"),
        dense_layers=sum(1 for _, f in kinds if f == "dense"),
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        dk=cfg["head_dim"], dv=cfg["v_head_dim"],
        swa_h=cfg["swa_num_attention_heads"],
        swa_kv=cfg["swa_num_key_value_heads"],
        swa_dk=cfg["swa_head_dim"], swa_dv=cfg["swa_v_head_dim"],
        sliding_window=cfg["sliding_window"],
        experts_held=cfg["n_routed_experts"],
        experts_routed=cfg["router_width"],
        top_k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        dense_f=cfg["intermediate_size"])


def attention_matrix_params(cfg, kind):
    """wq, wk, wv, wo of a full or a window layer."""
    s = dims(cfg)
    p = "swa_" if kind == "window" else ""
    h, kv, dk, dv = s[p + "h"], s[p + "kv"], s[p + "dk"], s[p + "dv"]
    return s["d"] * (h * dk + kv * dk + kv * dv) + h * dv * s["d"]


def expert_matrix_params(cfg):
    """One routed expert: gate, up and down."""
    s = dims(cfg)
    return 3 * s["d"] * s["f"]


def dense_matrix_params(cfg):
    s = dims(cfg)
    return 3 * s["d"] * s["dense_f"]


def experts_held(cfg):
    return dims(cfg)["experts_held"]


def model_params(cfg):
    """Everything held here: the blocks with their norms, sinks and the
    selection's correction, embedding, head and its bias."""
    s = dims(cfg)
    total = 2 * s["vocab"] * s["d"] + s["vocab"]
    for kind, ffn in s["pattern"]:
        total += attention_matrix_params(cfg, kind) + 2 * s["d"]
        if kind == "window" and cfg["add_swa_attention_sink_bias"]:
            total += s["swa_h"]
        if kind == "full" and cfg["add_full_attention_sink_bias"]:
            total += s["h"]
        total += (dense_matrix_params(cfg) if ffn == "dense" else
                  s["d"] * s["experts_routed"] + s["experts_routed"]
                  + s["experts_held"] * expert_matrix_params(cfg))
    return total


def matmul_params(cfg):
    """Parameters that a token multiplies here, on average at uniform
    routing: attention, the dense layer's SwiGLU, the router, its share
    ``top_k * held / routed`` of the held experts, the sliced head. The
    embedding is a lookup."""
    s = dims(cfg)
    routed = (s["top_k"] * s["experts_held"] / s["experts_routed"]
              * expert_matrix_params(cfg))
    total = s["d"] * s["vocab"]
    for kind, ffn in s["pattern"]:
        total += attention_matrix_params(cfg, kind)
        total += (dense_matrix_params(cfg) if ffn == "dense"
                  else s["d"] * s["experts_routed"] + routed)
    return total


def attention_flops_forward(cfg, queries, keys_per_query):
    """Over all layers: QK^T at the key width and PV at the value width
    for ``queries`` positions that each see ``keys_per_query`` keys in a
    full layer and at most ``sliding_window`` in a window layer."""
    s = dims(cfg)
    near = min(keys_per_query, s["sliding_window"])
    return 2.0 * queries * (
        s["full_layers"] * s["h"] * (s["dk"] + s["dv"]) * keys_per_query
        + s["window_layers"] * s["swa_h"] * (s["swa_dk"] + s["swa_dv"])
        * near)


def stored_width(width, lanes=128):
    """The width the program's cache keeps rows of ``width`` features at
    (``nn/hybrid.py``): up to 128 lanes as they are, beyond that padded to
    the next multiple, so keys of 192 are held as 256."""
    return width if width <= lanes else -(-width // lanes) * lanes


def kv_bytes_per_token(cfg, dtype_bytes):
    """K and V of one position, as stored, over the full layers held
    here: what a slot's pages grow by with every position. A window layer
    holds a ring a slot (``ring_bytes_per_slot``) and nothing a token."""
    s = dims(cfg)
    return (s["full_layers"] * s["kv"] * dtype_bytes
            * (stored_width(s["dk"]) + stored_width(s["dv"])))


def ring_bytes_per_slot(cfg, dtype_bytes, page_size=16):
    """What a slot holds of the window layers, whatever its context: the
    window's pages and one more of K and V rows, as stored, a layer."""
    s = dims(cfg)
    positions = (-(-s["sliding_window"] // page_size) + 1) * page_size
    return (s["window_layers"] * positions * s["swa_kv"] * dtype_bytes
            * (stored_width(s["swa_dk"]) + stored_width(s["swa_dv"])))


# -- what the served roofline's reader needs ----------------------------------

def expert_flops(cfg, assignments):
    """Forward of the routed experts' three matrices over ``assignments``
    held (row, expert) pairs."""
    return 2.0 * expert_matrix_params(cfg) * assignments


def expert_bytes(cfg, assignments, touched, weight_bytes=2, token_bytes=2):
    """The matrices of the ``touched`` (expert, layer, step) triples read
    once, and each assignment's row read and its result written."""
    s = dims(cfg)
    return (touched * expert_matrix_params(cfg) * weight_bytes
            + 2.0 * s["d"] * token_bytes * assignments)
