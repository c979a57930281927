"""Operations and bytes a decoder language model's train step needs, from
the configuration's shapes alone (never from XLA's `cost_analysis`).  A
multiply-add is 2 operations; backward costs twice forward; recomputation
(remat, the attention backward's rebuilt logits) is not counted.  Beside
`flops.py`, whose `TRAIN_FLOPS` is keyed by model: this file is keyed by
PART, because the cell's per-layer metrics time the parts apart.

All counts are for the configuration AS CUT: the layers, the held
experts and the vocabulary slice the file states.
"""

from __future__ import annotations


def layer_counts(config: dict) -> dict:
    """How many of each kind of block a step runs: every layer has MLA;
    the MTP module is one more MoE block, one more projection and one
    more pass through the head."""
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    mtp = config["num_nextn_predict_layers"]
    return {
        "mla": layers + mtp, "dense_ffn": dense,
        "moe": layers - dense + mtp, "head": 1 + mtp, "mtp": mtp,
    }


def mla_proj_flops_per_token(config: dict) -> float:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 2.0 * (
        d * config["q_lora_rank"]
        + config["q_lora_rank"] * heads * qk
        + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
        + config["kv_lora_rank"] * heads
        * (config["qk_nope_head_dim"] + config["v_head_dim"])
        + heads * config["v_head_dim"] * d
    )


def mla_core_flops_per_token(config: dict, seq_len: int) -> float:
    """q k^T and p v of one token's row in one layer, forward, THE CAUSAL
    HALF COUNTED: position t attends t + 1 keys, (L + 1) / 2 on
    average."""
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 2.0 * heads * (qk + config["v_head_dim"]) * (seq_len + 1) / 2


def swiglu_flops_per_token(hidden: int, width: int) -> float:
    return 2.0 * 3 * hidden * width


def routed_flops_per_row(config: dict) -> float:
    """One routing slot through one expert (gate, up, down)."""
    return swiglu_flops_per_token(
        config["hidden_size"], config["moe_intermediate_size"]
    )


def held_share(config: dict) -> float:
    """Routing slots that land on a held expert under balanced load."""
    return config["held_experts"][1] / config["n_routed_experts_published"]


def forward_flops_per_token(config: dict, seq_len: int,
                            routed_here: float = None) -> dict:
    """{part: matmul operations of one token, forward, over the whole
    cut model}.  `routed_here` is the share of the tokens x top_k slots
    that chose a held expert (`held_share` when not measured)."""
    if routed_here is None:
        routed_here = held_share(config)
    n = layer_counts(config)
    d = config["hidden_size"]
    shared = swiglu_flops_per_token(
        d, config["n_shared_experts"] * config["moe_intermediate_size"]
    )
    return {
        "mla_proj": n["mla"] * mla_proj_flops_per_token(config),
        "mla_core": n["mla"] * mla_core_flops_per_token(config, seq_len),
        "dense_ffn": n["dense_ffn"] * swiglu_flops_per_token(
            d, config["intermediate_size"]
        ),
        "moe_router": n["moe"] * 2.0 * d
        * config["n_routed_experts_published"],
        "moe_shared": n["moe"] * shared,
        "moe_experts": n["moe"] * routed_flops_per_row(config)
        * config["num_experts_per_tok"] * routed_here,
        "head": n["head"] * 2.0 * d * config["vocab_size"],
        "mtp_proj": n["mtp"] * 2.0 * 2 * d * d,
    }


def train_flops_per_token(config: dict, seq_len: int,
                          routed_here: float = None) -> float:
    return 3.0 * sum(
        forward_flops_per_token(config, seq_len, routed_here).values()
    )


def mla_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    """Every attention core of a step, forward (q k^T, p v) plus
    backward (dV, dP, dQ, dK: four products of the same size, twice the
    forward); the backward's rebuilt logits are recomputation."""
    tokens = traffic["minibatch_size"] * traffic["seq_len"]
    per_token = forward_flops_per_token(config, traffic["seq_len"])
    return 3.0 * per_token["mla_core"] * tokens


def mla_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """The least HBM traffic of the attention cores of a step in the
    stated 2-byte type: forward reads q, k, v and writes o; backward
    reads q, k, v, o, dO and writes dQ, dK, dV (log-sum-exp and delta
    are 1/256 of a row and left out)."""
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    tokens = traffic["minibatch_size"] * traffic["seq_len"]
    forward = 2 * qk + 2 * v
    backward = (2 * qk + 2 * v + v) + (2 * qk + v)
    return 2.0 * heads * (forward + backward) * tokens * layer_counts(
        config
    )["mla"]


def moe_experts_train_flops_per_step(config: dict, traffic: dict,
                                     routed_here: float) -> float:
    """The grouped products over the rows ACTUALLY routed here."""
    tokens = traffic["minibatch_size"] * traffic["seq_len"]
    per_token = forward_flops_per_token(
        config, traffic["seq_len"], routed_here
    )
    return 3.0 * per_token["moe_experts"] * tokens


def moe_experts_train_bytes_per_step(config: dict, traffic: dict,
                                     routed_here: float) -> float:
    """Least HBM traffic of the grouped products: each held expert's
    weights once forward and once for each of the backward's two uses
    (2-byte reads; the float32 gradient written once), and the routed
    rows in and out at 2 bytes."""
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    held = config["held_experts"][1]
    tokens = traffic["minibatch_size"] * traffic["seq_len"]
    rows = tokens * config["num_experts_per_tok"] * routed_here
    weights = held * 3 * d * width * (3 * 2 + 4)
    activations = rows * 2 * 3 * (d + 2 * width + width + d)
    return layer_counts(config)["moe"] * float(weights + activations)
