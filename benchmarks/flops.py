"""Operations and bytes the algorithms need, computed from shapes.

These are the benchmark's own counts: a later PR cannot change them.
Only useful work is counted — the causal, windowed part of attention;
nothing recomputed (remat, the backward kernels' second look at the
scores is part of the backward algorithm and is counted there).
"""

from __future__ import annotations


def attended_pairs(seq: int, window: int = 0) -> int:
    """Query-key pairs a causal (and, with ``window`` > 0, sliding-window)
    mask keeps in one sequence of ``seq`` tokens: query i sees
    min(i + 1, window) keys."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied by: every matrix but the
    embedding table (a lookup)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * (d + 2 * kv) + d * d + d * 2 * f + f * d
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def attention_flops_fwd(cfg: dict, seq: int) -> int:
    """Forward attention FLOP of one sequence, all layers and heads:
    QK^T and PV, 2 FLOP per multiply-add, on the attended pairs only."""
    d = cfg["hidden_size"]
    pairs = attended_pairs(seq, cfg.get("sliding_window") or 0)
    return cfg["num_hidden_layers"] * 4 * pairs * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOP per trained token: 6 per matrix parameter (forward 2,
    backward 4) plus three times the forward attention, recompute
    excluded."""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq


# ---------------------------------------------------------------- flash

def flash_call_shape(cfg: dict, batch: int, seq: int) -> dict:
    h = cfg["num_attention_heads"]
    return {"batch": batch, "seq": seq, "heads": h,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // h,
            "window": cfg.get("sliding_window") or 0}


def _qkvo_bytes(s: dict, itemsize: int) -> tuple:
    q = s["batch"] * s["seq"] * s["heads"] * s["head_dim"] * itemsize
    kv = s["batch"] * s["seq"] * s["kv_heads"] * s["head_dim"] * itemsize
    lse = s["batch"] * s["seq"] * s["heads"] * 4
    return q, kv, lse


def flash_fwd_cost(s: dict, itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of one forward call: scores and the weighted sum on
    the attended pairs; reads q, k, v once, writes o and the row
    log-sum-exp."""
    pairs = attended_pairs(s["seq"], s["window"])
    flop = s["batch"] * s["heads"] * 4 * pairs * s["head_dim"]
    q, kv, lse = _qkvo_bytes(s, itemsize)
    return flop, 2 * q + 2 * kv + lse


def flash_bwd_cost(s: dict, itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the backward pair (dq kernel + dk/dv kernel) for
    one forward call.  dq: scores, dP and dq = 3 products; dk/dv: scores,
    dP, dv and dk = 4 products; 2 FLOP per multiply-add on the attended
    pairs.  Each kernel reads q, k, v, do and the row statistics; they
    write dq, and dk, dv."""
    pairs = attended_pairs(s["seq"], s["window"])
    flop = s["batch"] * s["heads"] * 14 * pairs * s["head_dim"]
    q, kv, lse = _qkvo_bytes(s, itemsize)
    reads = 2 * (2 * q + 2 * kv + 2 * lse)
    return flop, reads + q + 2 * kv


def least_seconds(flop: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound binds: the
    larger of FLOP over peak FLOP/s and bytes over peak bytes/s."""
    t_flop = flop / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flop, t_mem), ("compute" if t_flop >= t_mem else "memory")


def roofline_share(flop: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """Share (%) of its roofline that a kernel reached in ``seconds``,
    and which bound binds."""
    least, bound = least_seconds(flop, nbytes, peak)
    return 100.0 * least / seconds, bound
