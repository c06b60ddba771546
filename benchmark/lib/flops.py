"""Operations and bytes, computed from shapes by the benchmark.

The program has a FLOP model of its own (``telemetry/stepmeter.py``); an MFU
or a roofline share divided by a count the program could change would move
with it, so these functions take the PUBLISHED keys of a configuration file
and nothing from the program.

Conventions: a multiply-add is 2 operations; the backward pass costs twice
the forward; recomputed operations are not counted; causal attention counts
the half of the score matrix that is needed."""

from __future__ import annotations


def _llama_like(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    attn = h * heads * d * 2 + h * kv * d * 2           # q, o + k, v
    mlp = 3 * h * cfg["intermediate_size"]              # gate, up, down
    return {"layers": cfg["num_hidden_layers"], "per_layer": attn + mlp,
            "small_per_layer": 2 * h, "final_norm": h,
            "embed": cfg["vocab_size"] * h,
            "head": 0 if cfg.get("tie_word_embeddings") else
            cfg["vocab_size"] * h,
            "head_matmul": cfg["vocab_size"] * h,
            "heads": heads, "kv_heads": kv, "head_dim": d}


def _gpt2_like(cfg: dict) -> dict:
    h, heads = cfg["n_embd"], cfg["n_head"]
    inner = cfg.get("n_inner") or 4 * h
    weights = 4 * h * h + 2 * h * inner                 # qkv, out, fc_in/out
    biases = 3 * h + h + inner + h
    return {"layers": cfg["n_layer"], "per_layer": weights,
            "small_per_layer": biases + 4 * h, "final_norm": 2 * h,
            "embed": (cfg["vocab_size"] + cfg["n_positions"]) * h,
            "head": 0, "head_matmul": cfg["vocab_size"] * h,
            "heads": heads, "kv_heads": heads, "head_dim": h // heads}


_FAMILIES = {"llama_like": _llama_like, "gpt2_like": _gpt2_like}


def shape_of(cfg: dict) -> dict:
    """Parameter counts by part, from a configuration file's published keys
    (``cfg["reference"]`` names the family)."""
    return _FAMILIES[cfg["reference"]](cfg)


def param_count(cfg: dict) -> int:
    s = shape_of(cfg)
    return (s["layers"] * (s["per_layer"] + s["small_per_layer"])
            + s["final_norm"] + s["embed"] + s["head"])


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Matrix multiplications of every layer and the head, plus causal
    attention at sequence length ``seq`` (mean over the positions)."""
    s = shape_of(cfg)
    matmul = 2 * (s["layers"] * s["per_layer"] + s["head_matmul"])
    # QK^T and PV: 2 * 2 * seq * heads * d per token, halved by causality
    attn = s["layers"] * 2 * seq * s["heads"] * s["head_dim"]
    return float(matmul + attn)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq)


def flash_fwd_ops_bytes(batch: int, seq: int, heads: int, kv_heads: int,
                        head_dim: int, itemsize: int = 2):
    """One causal flash-attention forward call: the operations it needs and
    the bytes it cannot avoid (q, k, v read once, o written once, the
    log-sum-exp written in float32)."""
    ops = 2.0 * batch * heads * seq * seq * head_dim     # 4 s^2 d / 2
    qo = 2 * batch * seq * heads * head_dim * itemsize
    kv = 2 * batch * seq * kv_heads * head_dim * itemsize
    lse = batch * heads * seq * 4
    return ops, float(qo + kv + lse)


def roofline_s(ops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which limit sets it."""
    t_ops = ops / peaks["flops_bf16"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
