"""Operations and bytes of the post-norm delta-rule hybrid that
``reference_olmo.py`` computes (the ``counts`` of ``olmo-hybrid-7b`` and
``tiny-olmo``): full multi-head attention layers with norms on the whole q
and k projections and no rotary among gated delta-rule layers, each with a
dense SwiGLU, post-norm; a final norm and an untied head with a bias.

Pure arithmetic on the configuration's sizes, no jax: the parent loads
this file (``modules.counts_of``). A product of (m, k) by (k, n) is
2*m*k*n operations. A delta-rule layer's mixing costs the same at any
context: ``DELTA_OPS_PER_STATE`` operations an element of its state a
token, which ``attention_flops_forward`` counts beside the full layers'
products over their keys.

``dims`` gives ``window: None``: every key is seen in a full layer, and a
delta-rule layer sees no keys.
"""

#: operations an element of a delta-rule state takes a token: the decay,
#: S^T k (a product and a sum), the outer product k d^T and its sum into
#: S, S^T q (a product and a sum)
DELTA_OPS_PER_STATE = 7
#: the step's q, k, v, g and beta are read, and o written, in float32
STEP_IO_BYTES = 4


def layer_kinds(cfg):
    """["full" | "linear"] of the layers held."""
    return ["full" if t == "full_attention" else "linear"
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def dims(cfg):
    kinds = layer_kinds(cfg)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        d=d, layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        window=None, pattern=kinds,
        full_layers=kinds.count("full"), linear_layers=kinds.count("linear"),
        h=h, kv=cfg["num_key_value_heads"], hd=d // h,
        f=cfg["intermediate_size"],
        nk=cfg["linear_num_key_heads"], nv=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"])


def full_matrix_params(cfg):
    """wq, wk, wv, wo of a full layer."""
    s = dims(cfg)
    return 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def linear_matrix_params(cfg):
    """in_qkvz (q, k, v and the gate z), in_ba and out_proj of a
    delta-rule layer."""
    s = dims(cfg)
    kd, vd = s["nk"] * s["dk"], s["nv"] * s["dv"]
    return s["d"] * (2 * kd + 2 * vd) + s["d"] * 2 * s["nv"] + vd * s["d"]


def swiglu_matrix_params(cfg):
    s = dims(cfg)
    return 3 * s["d"] * s["f"]


def conv_channels(cfg):
    """The convolution's channels: q, k and v side by side."""
    s = dims(cfg)
    return 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]


def layer_params(cfg, kind):
    """One block with its norms: the two after its sublayers; a full
    layer's q and k norms; a delta-rule layer's convolution, the decay's
    two and the head norm."""
    s = dims(cfg)
    total = swiglu_matrix_params(cfg) + 2 * s["d"]
    if kind == "full":
        return (total + full_matrix_params(cfg)
                + (s["h"] + s["kv"]) * s["hd"])
    return (total + linear_matrix_params(cfg) + conv_channels(cfg) * s["taps"]
            + 2 * s["nv"] + s["dv"])


def model_params(cfg):
    """Everything held here: the blocks, embedding, final norm, head and
    its bias."""
    s = dims(cfg)
    return (sum(layer_params(cfg, kind) for kind in s["pattern"])
            + 2 * s["vocab"] * s["d"] + s["d"] + s["vocab"])


def matmul_params(cfg):
    """Parameters that every token multiplies: every block's matrices and
    the head. The embedding is a lookup."""
    s = dims(cfg)
    return (s["full_layers"] * full_matrix_params(cfg)
            + s["linear_layers"] * linear_matrix_params(cfg)
            + s["layers"] * swiglu_matrix_params(cfg) + s["d"] * s["vocab"])


def state_elements(cfg):
    """Elements of one delta-rule layer's state a slot: value heads x key
    width x value width."""
    s = dims(cfg)
    return s["nv"] * s["dk"] * s["dv"]


def delta_flops_per_token(cfg):
    """The delta rule's mixing of one token over all delta-rule layers."""
    return (DELTA_OPS_PER_STATE * state_elements(cfg)
            * dims(cfg)["linear_layers"])


def attention_flops_forward(cfg, queries, keys_per_query):
    """QK^T and PV over the full layers for ``queries`` positions that each
    see ``keys_per_query`` keys, and the delta rule's mixing of the same
    positions, which sees no keys."""
    s = dims(cfg)
    return queries * (4.0 * s["full_layers"] * s["h"] * s["hd"]
                      * keys_per_query + delta_flops_per_token(cfg))


def kv_bytes_per_token(cfg, dtype_bytes):
    """K and V of one position over the full layers held here: what a
    slot's pages grow by with every position. A delta-rule layer grows by
    nothing (``state_bytes_per_slot``)."""
    s = dims(cfg)
    return 2 * s["full_layers"] * s["kv"] * s["hd"] * dtype_bytes


def state_bytes_per_slot(cfg, tail_bytes=2):
    """What a slot holds of the delta-rule layers, whatever its context:
    the state in float32 and the convolution's last ``taps - 1`` inputs a
    layer."""
    s = dims(cfg)
    return s["linear_layers"] * (
        4 * state_elements(cfg)
        + (s["taps"] - 1) * conv_channels(cfg) * tail_bytes)


# -- what the served roofline's reader needs ----------------------------------

def delta_step_flops(cfg, rows):
    """The state update of ``rows`` (live row, delta-rule layer) pairs, one
    token each."""
    return DELTA_OPS_PER_STATE * state_elements(cfg) * rows


def delta_step_bytes(cfg, rows):
    """Each row's state (float32) read and written once, its q and k
    (value heads x key width), v (x value width), g and beta read and o
    written, in float32."""
    s = dims(cfg)
    io = s["nv"] * (2 * s["dk"] + 2 * s["dv"] + 2)
    return rows * (2 * 4 * state_elements(cfg) + STEP_IO_BYTES * io)
