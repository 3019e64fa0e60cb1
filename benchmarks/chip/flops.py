"""Model FLOPs per token, from a config's widths.

Counted: every matmul of the model as published (2 FLOPs per multiply-
add), causal attention over the lower triangle only, and the LM head.
Not counted: the embedding lookup (a gather, whatever the program makes
of it), norms, activations, the optimizer, and any recomputation.
Training is forward plus backward, 3x the forward.
"""
from __future__ import annotations


def dense_forward(c: dict, seq: int) -> float:
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    layer = (2 * d * h * hd            # q
             + 2 * 2 * d * kv * hd     # k, v
             + 2 * h * hd * d          # o
             + 3 * 2 * d * f           # gate, up, down
             + 2 * 2 * h * hd * (seq + 1) / 2)   # QK^T and PV, causal mean
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"]


def forward_per_token(config: dict, seq: int) -> float:
    if config["family"] == "dense":
        return dense_forward(config, seq)
    raise ValueError(f"no FLOP count for family {config['family']!r}")


def train_step(config: dict, batch: int, seq: int) -> float:
    """One optimizer step over ``batch`` x ``seq`` tokens."""
    return 3 * forward_per_token(config, seq) * batch * seq


def eval_pass(config: dict, batch: int, seq: int) -> float:
    """One forward over ``batch`` x ``seq`` tokens."""
    return forward_per_token(config, seq) * batch * seq
