"""Operations and bytes the Nemotron-H decoder's train step needs, from the
configuration's shapes alone (never from XLA's `cost_analysis`), by PART,
as `flops_granite.py` counts the granite decoder's.  A multiply-add is 2
operations; backward costs twice forward; recomputation (remat, the
attention backward's rebuilt logits, the scan backward's rebuilt chunk, the
conv backward's rebuilt z, the walk's rebuilt forward) is not counted.

All counts are for the configuration AS CUT: the published layers in
`layers_held`, the held experts and the vocabulary slice the file states.
A layer is ONE part: a Mamba-2 mixer, a routed layer or attention.

    ssd core     the state-space RECURRENCE, whatever implements it: a
                 token and head decays the (P, N) state (P N), writes the
                 rank-one update dt x B^T (2 P N and P), reads S C (2 P N)
                 and adds the skip D x (2 P).  Its least traffic is x, y
                 and their gradients once in the stated type, B, C and
                 theirs once FOR EACH OF THE 8 GROUPS (a token has eight
                 of each: 1,024 columns, where one group has 128), dt and
                 its gradient once in float32; the boundary states, the
                 running sums, the eight C B^T a chunk and everything a
                 chunked form rebuilds are the implementation's, not the
                 mathematics', so a chunk size or a rebuilt state lowers a
                 share of this roofline and nothing lifts it over 100%.
    short conv   y = silu(conv_K(u) + b) over the x | B | C columns
                 (6,144): forward reads u and writes y, backward reads u
                 and dy and writes du; the K x W taps and the bias are
                 nothing beside them.
    gqa core     q k^T and p v of the attention layer at 32 heads of 128
                 over 2 K/V heads, THE CAUSAL HALF COUNTED; the backward's
                 four products (dV, dP, dQ, dK) are twice the forward, its
                 rebuilt logits are recomputation and are not counted.
    moe experts  the grouped products over the rows ACTUALLY routed here:
                 TWO products an expert (up, down: no gate projection) and
                 their transposes, 4 d w operations a row forward.
"""

from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
BYTES = 2          # the stated type, bfloat16
FLOAT32 = 4


def layers(config: dict) -> list:
    """The kind of each layer the cut model has."""
    pattern = config["hybrid_override_pattern"]
    return [pattern[i] for i in config["layers_held"]]


def count(config: dict, kind: str) -> int:
    return sum(1 for k in layers(config) if k == kind)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


def ssm_sizes(config: dict):
    """(heads, head width, channels, state columns a group x groups) of a
    Mamba-2 layer."""
    heads, dim = config["mamba_num_heads"], config["mamba_head_dim"]
    return (
        heads, dim, heads * dim, config["n_groups"] * config["ssm_state_size"]
    )


def conv_columns(config: dict) -> int:
    """x | B | C: what the conv passes over."""
    _, _, inner, shared = ssm_sizes(config)
    return inner + 2 * shared


def held_share(config: dict) -> float:
    """Routing slots that land on a held expert under balanced load."""
    return config["held_experts"][1] / config["n_routed_experts_published"]


def ssm_proj_flops_per_token(config: dict) -> float:
    """in_proj (z | xBC | dt) and out_proj."""
    d = config["hidden_size"]
    heads, _, inner, _ = ssm_sizes(config)
    return 2.0 * (d * (inner + conv_columns(config) + heads) + inner * d)


def ssd_core_flops_per_token(config: dict) -> float:
    """The recurrence of one token in one layer, forward."""
    heads, dim, _, _ = ssm_sizes(config)
    return float(heads) * (5 * dim * config["ssm_state_size"] + 3 * dim)


def attn_core_flops_per_token(config: dict, seq_len: int) -> float:
    """q k^T and p v of one token's row in one layer, forward, the causal
    half: position t attends t + 1 keys, (L + 1) / 2 on average."""
    return (
        2.0 * config["num_attention_heads"] * 2 * config["head_dim"]
        * (seq_len + 1) / 2
    )


def relu2_flops_per_token(hidden: int, width: int) -> float:
    """up and down: no gate projection."""
    return 2.0 * 2 * hidden * width


def forward_flops_per_token(config: dict, seq_len: int,
                            routed_here: float = None) -> dict:
    """{part: operations of one token, forward, over the whole cut
    model}.  `routed_here` is the share of the tokens x top_k slots that
    chose a held expert (`held_share` when not measured)."""
    if routed_here is None:
        routed_here = held_share(config)
    d, heads = config["hidden_size"], config["num_attention_heads"]
    dim, kv = config["head_dim"], config["num_key_value_heads"]
    parts = dict.fromkeys((
        "ssm_proj", "ssd_core", "attn_proj", "attn_core", "moe_router",
        "moe_shared", "moe_experts",
    ), 0.0)
    for kind in layers(config):
        if kind == MAMBA:
            parts["ssm_proj"] += ssm_proj_flops_per_token(config)
            parts["ssd_core"] += ssd_core_flops_per_token(config)
        elif kind == ATTENTION:
            parts["attn_proj"] += 2.0 * d * dim * (2 * heads + 2 * kv)
            parts["attn_core"] += attn_core_flops_per_token(config, seq_len)
        else:
            parts["moe_router"] += 2.0 * d * config[
                "n_routed_experts_published"
            ]
            parts["moe_shared"] += relu2_flops_per_token(
                d, config["moe_shared_expert_intermediate_size"]
            )
            parts["moe_experts"] += (
                relu2_flops_per_token(d, config["moe_intermediate_size"])
                * config["num_experts_per_tok"] * routed_here
            )
    parts["head"] = 2.0 * d * config["vocab_size"]
    return parts


def train_flops_per_token(config: dict, seq_len: int,
                          routed_here: float = None) -> float:
    return 3.0 * sum(
        forward_flops_per_token(config, seq_len, routed_here).values()
    )


def ssd_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["ssd_core"]


def ssd_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """x, y and their gradients once, every group's B, C and theirs once
    (2 bytes), dt and its gradient once (float32)."""
    heads, _, inner, shared = ssm_sizes(config)
    per_token = 4 * inner * BYTES + 4 * shared * BYTES + 2 * heads * FLOAT32
    return float(per_token) * tokens_per_step(traffic) * count(config, MAMBA)


def short_conv_train_flops_per_step(config: dict, traffic: dict) -> float:
    """An element costs 2K operations (the taps and the bias) and silu's 4
    forward, the K multiplies and K - 1 adds of du, the K multiplies and K
    adds of dw, the bias's add and silu's slope (6) backward."""
    taps = config["conv_kernel"]
    per_element = (2 * taps + 4) + (4 * taps + 6)
    return (
        float(per_element) * tokens_per_step(traffic)
        * conv_columns(config) * count(config, MAMBA)
    )


def short_conv_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Two streams of tokens x 6,144 forward (u, y), three backward (u,
    dy, du)."""
    return (
        float(BYTES) * (2 + 3) * tokens_per_step(traffic)
        * conv_columns(config) * count(config, MAMBA)
    )


def gqa_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    """Every attention core of a step, forward (q k^T, p v) plus backward
    (dV, dP, dQ, dK: twice the forward)."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["attn_core"]


def gqa_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, dO
    and writes dQ, dK, dV; k, v, dK and dV are Hkv heads wide."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    forward = 2 * heads + 2 * kv
    backward = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return (
        float(BYTES) * config["head_dim"] * (forward + backward)
        * tokens_per_step(traffic) * count(config, ATTENTION)
    )


def moe_experts_train_flops_per_step(config: dict, traffic: dict,
                                     routed_here: float) -> float:
    """The grouped products over the rows ACTUALLY routed here."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"], routed_here
    )["moe_experts"]


def moe_experts_train_bytes_per_step(config: dict, traffic: dict,
                                     routed_here: float) -> float:
    """Least HBM traffic of the grouped products: each held expert's two
    stacks once forward and once for each of the backward's two uses
    (2-byte reads; the float32 gradient written once), and the routed
    rows in and out at 2 bytes: the rows, the up product, the activation
    and the output, forward and for each of the backward's two uses."""
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    rows = (
        tokens_per_step(traffic) * config["num_experts_per_tok"] * routed_here
    )
    weights = config["held_experts"][1] * 2 * d * width * (3 * BYTES + FLOAT32)
    activations = rows * BYTES * 3 * (d + width + width + d)
    return count(config, EXPERTS) * float(weights + activations)
