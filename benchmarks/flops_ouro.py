"""Operations and bytes the Ouro decoder's train step needs, from the
configuration's shapes alone (never from XLA's `cost_analysis`), by PART,
as `flops_smallthinker.py` counts the SmallThinker decoder's.  A
multiply-add is 2 operations; backward costs twice forward; recomputation
(remat's rebuild, the attention backward's rebuilt logits) is not counted.

All counts are for the configuration AS CUT: N = the published layers in
`layers_held`, the whole vocabulary.  The stack is applied R =
`total_ut_steps` times a step over one set of weights, so a step holds
R x N block applications and R head passes, however few the weights:

    attn proj   q, k, v and o of one application: 4 x 2,048 x 2,048
    gqa core    one application's q k^T and p v at 16 heads of 128 over 16
                K/V heads, THE CAUSAL HALF COUNTED: position t attends
                t + 1 keys (33,558,528 pairs a head at 8,192)
    dense ffn   gate, up and down of one application: 3 x 2,048 x 5,632
    head        one pass of the untied head over the whole vocabulary
    exit gate   one logit a token after each of the first R - 1 trips
    bytes       the core's: q, o and their gradients once a QUERY head, k,
                v and their gradients once a K/V head, every application

At the cell's sizes a block application is 0.842e12 in its products +
0.275e12 in its core forward, a head pass 1.649e12.
"""

from __future__ import annotations

BYTES = 2          # the stated type, bfloat16


def depth(config: dict) -> int:
    return len(config["layers_held"])


def trips(config: dict) -> int:
    return config["total_ut_steps"]


def applications(config: dict) -> int:
    """Block applications of one forward: every held layer once a trip."""
    return trips(config) * depth(config)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


def pairs_per_head(seq_len: int) -> int:
    """(query, key) pairs one head scores over one causal sequence."""
    return seq_len * (seq_len + 1) // 2


def core_flops_per_token(config: dict, seq_len: int) -> float:
    """q k^T and p v of one token's row in ONE application, forward."""
    return (
        2.0 * config["num_attention_heads"] * 2 * config["head_dim"]
        * pairs_per_head(seq_len) / seq_len
    )


def attn_proj_flops_per_token(config: dict) -> float:
    """q, k, v and o of ONE application."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * d * dim * (2 * heads + 2 * kv)


def dense_ffn_flops_per_token(config: dict) -> float:
    """gate, up and down of ONE application."""
    return 2.0 * 3 * config["hidden_size"] * config["intermediate_size"]


def head_flops_per_token(config: dict) -> float:
    """ONE pass of the head."""
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    """{part: matmul operations of one token, forward, over the whole
    step's forward: R x N applications, R head passes, R - 1 gate
    logits}."""
    times = applications(config)
    return {
        "attn_proj": times * attn_proj_flops_per_token(config),
        "gqa_core": times * core_flops_per_token(config, seq_len),
        "dense_ffn": times * dense_ffn_flops_per_token(config),
        "head": trips(config) * head_flops_per_token(config),
        "exit_gate": (trips(config) - 1) * 2.0 * config["hidden_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())


def core_train_flops_per_step(config: dict, traffic: dict) -> float:
    """Every attention core of a step, forward (q k^T, p v) plus backward
    (dV, dP, dQ, dK: four products of the same size, twice the forward);
    the backward's rebuilt logits are recomputation."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["gqa_core"]


def core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """The least HBM traffic of a step's attention cores in the stated
    2-byte type: forward reads q, k, v and writes o; backward reads q, k,
    v, o, dO and writes dQ, dK, dV; k, v, dK and dV are Hkv heads wide
    (log-sum-exp and delta are 1/128 of a row and left out); once an
    application."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    forward = 2 * heads + 2 * kv
    backward = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return (
        float(BYTES) * config["head_dim"] * (forward + backward)
        * tokens_per_step(traffic) * applications(config)
    )
