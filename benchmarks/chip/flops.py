"""Model FLOPs of training a dense decoder, for the ``mfu`` metric.

The arithmetic is that of the program's roofline model
(``repro.roofline.analysis.forward_flops`` for attention + SwiGLU layers),
copied here so that no later change to the program can change the
yardstick:

- a matmul of (m, k) by (k, n) counts 2*m*k*n operations;
- causal attention counts the S*(S+1)/2 attended (query, key) pairs of
  each sequence, once for the scores and once for the weighted values;
- the output head (tied or not) counts once; embedding lookups, norms,
  softmax and the optimizer count nothing;
- training is 3 x forward (the backward pass is twice the forward), and
  work recomputed under rematerialisation is not counted.
"""
from __future__ import annotations


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of ``batch`` sequences of ``seq`` tokens.

    ``cfg`` uses the configuration files' keys (``configs/*.json``).
    """
    E = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KH = cfg["num_key_value_heads"]
    D = cfg["head_dim"]
    F = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    T = batch * seq
    proj = 2 * T * E * (H * D + 2 * KH * D) + 2 * T * H * D * E
    scores = 2 * batch * (seq * (seq + 1) / 2) * H * D * 2
    ffn = 2 * T * E * F * 3
    return cfg["num_hidden_layers"] * (proj + scores + ffn) + 2 * T * E * V


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token at sequence length ``seq``."""
    return 3.0 * forward_flops(cfg, 1, seq) / seq
