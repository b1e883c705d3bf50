"""Operations and bytes from shapes: the benchmark's own counts.

Sizes come from a configuration file under ``chipbench/configs`` (the
model's public ``config.json``, named in the file's ``source``). The
counts follow the algorithm, not the implementation: a matrix product of
(m, k) by (k, n) is 2*m*k*n operations, the backward pass of a layer is
twice its forward, and nothing recomputed counts. Peaks are in
``chipbench/peaks.json`` (Google Cloud documentation, "TPU v5e").
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind):
    """The row of ``peaks.json`` for this ``device_kind``; a device that
    is not in the table is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError("no peaks for device kind %r in peaks.json"
                       % device_kind)
    return peaks[device_kind]


def dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, f=cfg["intermediate_size"], h=h,
                kv=cfg["num_key_value_heads"], hd=hd,
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                window=cfg.get("sliding_window"))


def layer_matrix_params(cfg):
    """wq, wk, wv, wo and the three SwiGLU matrices of one block."""
    s = dims(cfg)
    q_d, kv_d = s["h"] * s["hd"], s["kv"] * s["hd"]
    return (s["d"] * q_d + 2 * s["d"] * kv_d + q_d * s["d"]
            + 3 * s["d"] * s["f"])


def layer_params(cfg):
    """One block with its two RMSNorm gains."""
    return layer_matrix_params(cfg) + 2 * cfg["hidden_size"]


def model_params(cfg):
    """Everything held here: blocks, embedding, head and its bias."""
    s = dims(cfg)
    return (s["layers"] * layer_params(cfg) + 2 * s["vocab"] * s["d"]
            + s["vocab"])


def matmul_params(cfg):
    """Parameters that every token multiplies: the blocks' matrices and
    the head. The embedding is a lookup."""
    s = dims(cfg)
    return s["layers"] * layer_matrix_params(cfg) + s["d"] * s["vocab"]


def context_mean(t, window=None):
    """Mean number of keys a query attends to over positions 0..t-1 of a
    causal sequence, the key at its own position included."""
    if not window or window >= t:
        return (t + 1) / 2.0
    w = window
    return (w * (w + 1) / 2.0 + (t - w) * w) / t


def attention_flops_forward(cfg, queries, keys_per_query):
    """QK^T and PV over all layers for ``queries`` positions that each
    see ``keys_per_query`` keys (a mean is fine)."""
    s = dims(cfg)
    return 4.0 * s["layers"] * s["h"] * s["hd"] * queries * keys_per_query


def train_flops_per_token(cfg, seq_len):
    """Forward and backward model operations per trained token at
    ``seq_len``, causal: 6 per matrix parameter, and three times the
    forward attention products. Recomputation is not counted."""
    attn = attention_flops_forward(
        cfg, 1, context_mean(seq_len, dims(cfg)["window"]))
    return 6.0 * matmul_params(cfg) + 3.0 * attn


def forward_flops(cfg, new_tokens, keys_per_query):
    """Model operations of a forward pass over ``new_tokens`` positions
    (prefill or decode) that see ``keys_per_query`` keys each."""
    return (2.0 * matmul_params(cfg) * new_tokens
            + attention_flops_forward(cfg, new_tokens, keys_per_query))


def token_flops(cfg, prompt_len, i):
    """Model operations that give output token ``i`` of a request: the
    prefill over the prompt for the first, one position for each later
    one."""
    w = dims(cfg)["window"]
    if i == 0:
        return forward_flops(cfg, prompt_len, context_mean(prompt_len, w))
    ctx = prompt_len + i
    return forward_flops(cfg, 1, min(ctx, w) if w else ctx)


def request_flops(cfg, prompt_len, n_out):
    """Model operations to serve one request of ``n_out`` tokens."""
    return sum(token_flops(cfg, prompt_len, i) for i in range(n_out))


def kv_bytes_per_token(cfg, dtype_bytes):
    """K and V of one position over all layers held here."""
    s = dims(cfg)
    return 2 * s["kv"] * s["hd"] * dtype_bytes * s["layers"]


def weight_bytes(cfg, dtype_bytes):
    return model_params(cfg) * dtype_bytes


def flash_call_flops(batch_heads, seq_len, head_dim, causal=True,
                     window=None, backward=False):
    """Operations one flash-attention call needs over ``batch_heads``
    (sequences x query heads) of ``seq_len``. Forward: QK^T and PV over the
    keys a query may see. Backward: dV, dP, dQ, dK, four products of the
    same size (the recomputed QK^T is not counted)."""
    ctx = context_mean(seq_len, window) if causal else float(seq_len)
    product = 2.0 * batch_heads * seq_len * ctx * head_dim
    return (4.0 if backward else 2.0) * product


DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s8": 1, "f8e4m3fn": 1}


def hlo_bytes(types):
    """Bytes of HLO array types as a trace names them: ["f32[32,4096,128]",
    "bf16[16,4096,128]"] -> 32*4096*128*4 + 16*4096*128*2."""
    total = 0
    for t in types:
        dtype, _, shape = t.rstrip("]").partition("[")
        n = 1
        for d in shape.split(","):
            n *= int(d) if d else 1
        total += n * DTYPE_BYTES[dtype]
    return total


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take, and which bound applies."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "bytes")
