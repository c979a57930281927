"""What the zoo's ten decoder language models have in common
(`model_zoo/glm/glm_moe_lite.py`, `laguna/laguna.py`, `lfm2/lfm2_moe.py`,
`kimi/kimi_linear.py`, `granite/granite_hybrid.py`,
`nemotron/nemotron_h.py`, `qwen3_next/qwen3_next.py`,
`smallthinker/smallthinker.py`, `ouro/ouro.py`,
`olmo_hybrid/olmo_hybrid.py`): RMSNorm (its scale
plain or zero-centred), its gated form (one statistic a group of
channels, the gate before the norm or after it) and its whole-width form
(`WholeWidthNorm`: one statistic over every head's columns together),
rotary's turn whole or
over a head's first columns (`Rope`, `partial_rotary`; `ops/rotary.py`'s
one-pass kernel where the shapes tile, the share of a layer's turn it took
sown beside it: `sow_rope_one_pass`), the seeds of a
decay (`a_log_init`, `dt_bias_init`), the bias-free dense layer, SwiGLU
and the non-gated squared-ReLU MLP, grouped-query attention
(`GroupedAttention`: no positions, no band, no norms and no gate unless
asked for; a head-wise or a whole-width QK-norm; only the HEADS a chip
holds where told `held_heads`, its part of the output and the norm's sum
of squares summed over a named axis where one is given: `held_of`,
`summed_over`), the routed block around `layers/moe.py: RoutedExperts` with
its shared expert (gated where asked for) and its routing's source (the
block's input where asked for), the cross-entropy
taken in blocks of tokens (its gradient made in the pass that makes a
block's logits), the per-position losses against the ids shifted (of one
state or of several at once, under the rows' own weights where given),
the blocks'
rematerialisation (`remat_block`, and what more of a block it keeps where
the device has room, however many times a step the stack is applied:
`remat_blocks`), and the zoo
functions a next-token model shares (`loss`, `optimizer`,
`eval_metrics_fn`, `param_sharding`)."""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.ad_checkpoint import checkpoint_name

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers import moe, step_metrics
from elasticdl_tpu.layers.embedding import embedding_param_sharding
from elasticdl_tpu.layers.moe import (
    RELU2,
    SIGMOID,
    SWIGLU,
    RoutedExperts,
    moe_param_sharding,
    walk_bytes,
)
from elasticdl_tpu.layers.step_metrics import STEP_METRICS, sow_step_metric
from elasticdl_tpu.ops import flash_attention, gdn, kda, ssd
from elasticdl_tpu.ops.rotary import one_pass_ok, rotary_turn
from elasticdl_tpu.worker.trainer import remat_kept_ratio

# What a rematerialised block keeps from its forward, by name: ONE policy
# for every decoder of the zoo.
SAVED_NAMES = (
    flash_attention.SAVED_NAMES + kda.SAVED_NAMES + ssd.SAVED_NAMES
    + gdn.SAVED_NAMES + moe.SAVED_NAMES
)

# What the gates below sow into STEP_METRICS (a gate that closes silences
# its layer): leaf name -> gauge by layer.
step_metrics.declare(
    "query_gate_mean_ratio",
    metrics_lib.default_registry().gauge(
        "worker_attention_query_gate_mean_ratio",
        "mean of an attention layer's query-wide sigmoid output gate over "
        "tokens, heads and a head's columns, last step of the task (a gate "
        "that closes silences its layer)",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "shared_gate_mean_ratio",
    metrics_lib.default_registry().gauge(
        "worker_moe_shared_gate_mean_ratio",
        "mean of a routed layer's sigmoid gate on its shared expert over "
        "tokens, last step of the task (0: the shared expert is silent)",
        labelnames=("layer",),
    ),
)

# Tokens whose logits exist at once in the cross-entropy.
CE_BLOCK = 2048


def rms_norm(x, scale, eps: float):
    """Statistics in float32 whatever `x` is; float32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


class RMSNorm(nn.Module):
    """`zero_centred`: the learned `scale` is w of a scale 1 + w, seeded
    off 0 so that the two forms differ at the seeded weights."""

    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        if self.zero_centred:
            scale = 1.0 + self.param(
                "scale", nn.initializers.normal(0.1), (x.shape[-1],)
            )
        else:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps).astype(self.dtype)


# A statistic a GROUP of channels, taken where the channels lie.  The plain
# way, `rms_norm` over a (..., groups, channels / groups) view, costs a
# layer's arrays their layout on the chip: a (2, 8192, 4096) float32 array
# is tiled (8 tokens x 128 channels), the view's reduce wants (8 groups x
# 128 channels), and XLA copies the whole float32 gated product from one
# tiling to the other before each of the three reduces (forward, the
# remat's forward, backward), writes the spread-back statistic out as a
# whole (..., 8, 512) array and copies that back three times: 10.5 GB of
# traffic a layer where one group moves 3.3.  Here the sum of squares and
# the spread-back are products with a 0/1 (channels, groups) matrix, so
# every whole array stays (tokens, channels) with the channels along the
# lanes; at `precision="highest"` the products are exact in float32 (the
# matrix is 0s and 1s; the default would round the squares to bfloat16).
# Placed by PR 52's chip probe (v5e; Nemotron's Mamba-2 layer alone, (2,
# 8192) tokens of 2,688, 4,096 channels in 8 groups, forward + backward
# under the zoo's remat, three traced calls; ms a layer: the whole layer |
# `ssm/gated_norm` | copies and fusions without a scope | `ssm/out`):
#   `rms_norm` over the view                   55.90 | 9.73 | 2.47 | 7.48
#   0/1 products (this)                        45.86 | 3.89 | 0.01 | 6.17
#   the sums a product, `jnp.repeat` back      49.85 | 5.20 | 0.83 | 7.64
#   static 512-wide slices, autodiff           48.46 | 4.98 | 2.13 | 6.44
#   the same, sliced before the gate           46.75 | 3.91 | 1.08 | 6.30
#   `lax.reduce_window` + `jnp.repeat`         55.15 | 9.10 | 0.83 | 9.17
#   slices in a jnp `custom_vjp`               49.53 | 4.53 | 1.29 | 7.64
#   Pallas, a (512 rows, group) block          45.66 | 2.28 | 1.32 | 5.97
# The kernel pair ties it (XLA copies the z slice out for them, twice a
# step) and would need this form beside it where a group is no whole lane
# tile (PERF.md section 6, PR 52).
# The same a HEAD of 128 in Kimi's delta-rule layer (`model_zoo/kimi/
# kimi_linear.py: HeadRMSNorm`, 32 groups), by PR 56's chip probe (v5e; the
# KDA layer alone, (2, 8192) tokens of 2,304, 32 heads of 128, bfloat16,
# forward + backward under the zoo's remat, three traced calls; ms a layer:
# the whole layer | `kimi/kda/out` | `kimi/kda/gate` | copies and fusions
# without a scope), and beside it the cell's traced step, ms a KDA layer
# (`out` | `gate` | `core`'s rebuild: the layer alone rebuilds no `Wo` and
# lays its decay out as the step does not):
#   norm, gate and decay over the (.., 32, 128) view
#                              88.16 | 17.60 | 3.42 | 0.00   22.14 | 6.39 | 9.35
#   norm and gate where the channels lie
#                              81.62 | 11.10 | 3.53 | 0.00   14.17 | 6.42 | 9.36
#   and the decay              81.61 | 11.10 | 3.53 | 0.00   14.04 | 4.16 | 8.53
def grouped_rms_norm(x, scale, eps: float, groups: int):
    """`rms_norm` with one statistic for each of `groups` equal runs of
    the last axis's channels; float32 out."""
    x = x.astype(jnp.float32)
    width = x.shape[-1]
    member = (
        jnp.arange(width)[:, None] // (width // groups) == jnp.arange(groups)
    ).astype(jnp.float32)
    mean = jnp.dot(jnp.square(x), member, precision="highest") / (
        width // groups
    )
    return x * jnp.dot(
        jax.lax.rsqrt(mean + eps), member.T, precision="highest"
    ) * scale


class GatedRMSNorm(nn.Module):
    """rms_norm(y * silu(z)) * scale with `gate_first` (a state-space
    mixer's output norm), rms_norm(y) * scale * silu(z) without (a delta
    rule's): ONE statistic for each of `groups` equal runs of the last
    axis's channels (a statistic a group of heads, or a head) and one
    learned scale over all of them, or, with `shared_scale`, one over a
    group's channels that every group shares; with one group the norm is
    over the whole last axis."""

    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    groups: int = 1
    gate_first: bool = True
    shared_scale: bool = False

    @nn.compact
    def __call__(self, y, z):
        width = y.shape[-1]
        if self.shared_scale:
            scale = jnp.tile(self.param(
                "scale", nn.initializers.ones, (width // self.groups,)
            ), self.groups)
        else:
            scale = self.param("scale", nn.initializers.ones, (width,))
        y = y.astype(jnp.float32)
        gate = jax.nn.silu(z.astype(jnp.float32))
        if self.gate_first:
            y = y * gate
        if self.groups == 1:
            y = rms_norm(y, scale, self.eps)
        else:
            y = grouped_rms_norm(y, scale, self.eps, self.groups)
        return (y if self.gate_first else y * gate).astype(self.dtype)


# What an attention layer that turns its queries and keys sows into
# STEP_METRICS: a later shape that falls off the kernel shows here, not as
# a slower step nobody can name.
step_metrics.declare(
    "rope_one_pass_ratio",
    metrics_lib.default_registry().gauge(
        "worker_rope_one_pass_ratio",
        "share of the query and key elements a layer's rotary embedding "
        "turns that the one-pass kernel turned (`ops/rotary.py`; the rest "
        "went through float32 halves in plain jnp), last step of the task; "
        "a layer that turns nothing reports nothing",
        labelnames=("layer",),
    ),
)


def rotary(x, theta: float, first: int = 0):
    """Plain rotary embedding over a head's columns from `first` on (all
    of them unless said) of (B, L, H, D) (the row of the catalog does not
    say which pairing the checkpoint uses; with seeded weights the two
    differ by a permutation of columns)."""
    width = x.shape[-1] - first
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    return rotary_turn(x, inv_freq, first=first)


class Rope(NamedTuple):
    """One kind of layer's rotary table: the first `columns` of a head
    turn at `inv_freq` (a tuple, so that a config hashes), cos and sin
    times `factor`."""

    columns: int
    inv_freq: Tuple[float, ...]
    factor: float


def plain_rope(head_dim: int, theta: float, share: float = 1.0) -> Rope:
    """theta ** (-2i / R) over the first R = `share` of a head's
    columns."""
    columns = int(head_dim * share)
    inv_freq = float(theta) ** (
        -np.arange(0, columns, 2, dtype=np.float64) / columns
    )
    return Rope(columns, tuple(inv_freq.tolist()), 1.0)


def partial_rotary(x, rope: Rope):
    """Turn the first `rope.columns` columns of (B, L, H, D)."""
    return rotary_turn(
        x, jnp.asarray(rope.inv_freq, jnp.float32), rope.factor
    )


def sow_rope_one_pass(module, columns: int, q_shape, k_shape,
                      q_first: int = 0) -> None:
    """Sow the share of the elements a layer turns that the kernel takes:
    (B, L, H, D) queries and keys, each turned over `columns` of a head,
    the queries' from column `q_first` on."""
    sizes = [
        (np.prod(shape[:3]) * columns, one_pass_ok(shape, columns, first))
        for shape, first in ((q_shape, q_first), (k_shape, 0))
    ]
    sow_step_metric(
        module, "rope_one_pass_ratio",
        sum(size for size, ok in sizes if ok) / sum(size for size, _ in sizes),
    )


def tap_init(key, shape, dtype=jnp.float32):
    """A depthwise kernel (K, d): uniform in +-1 / sqrt(K), K its fan-in."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def a_log_init(key, shape, dtype=jnp.float32):
    """log A, A uniform in [1, 16] a head, as the family's code seeds it."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1(dt), dt log-uniform in [1e-3, 1e-1], as the family's
    code seeds it: the decay is neither 0 nor 1 at the seeded weights."""
    low, high = np.log(1e-3), np.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, low, high))
    return dt + jnp.log(-jnp.expm1(-dt))


def dense(features: int, name: str, dtype, kind: str = ""):
    """The bias-free projection every decoder's products go through.
    With a `kind` (one of `PRODUCT_NAMES`) the output carries that name,
    for the policy of a block that keeps it (`remat_block`); a name no
    policy lists is the identity and lowers to nothing."""
    layer = nn.Dense(features, use_bias=False, name=name, dtype=dtype)
    if not kind:
        return layer
    return lambda x: checkpoint_name(layer(x), kind)


class SwiGLU(nn.Module):
    """(silu(x Wg) * (x Wu)) Wd, gate and up in one kernel.  `down_kind`
    names the last product for the plan (`FFN_OUT`) in a block that norms
    the MLP's output, whose backward reads it; unnamed everywhere else."""

    hidden: int
    width: int
    dtype: jnp.dtype = jnp.float32
    down_kind: str = ""

    @nn.compact
    def __call__(self, x):
        gate, up = jnp.split(
            dense(2 * self.width, "gate_up", self.dtype, GATE_UP)(x), 2,
            axis=-1,
        )
        # the block's last product: no backward reads its output, unless
        # the block norms it
        return dense(self.hidden, "down", self.dtype, self.down_kind)(
            nn.silu(gate) * up
        )


class ReLU2MLP(nn.Module):
    """(relu(x Wu))^2 Wd: no gate projection.  `up` carries the `gate_up`
    name (the MLP's first product, which `remat_blocks` may keep)."""

    hidden: int
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        up = dense(self.width, "up", self.dtype, GATE_UP)(x)
        return dense(self.hidden, "down", self.dtype)(
            jnp.square(nn.relu(up))
        )


# The MLP of an expert's form (`layers/moe.py: FORMS`): the shared expert
# is what a routed one is.
MLP_OF_FORM = {SWIGLU: SwiGLU, RELU2: ReLU2MLP}


def held_of(heads: int, held: Optional[Tuple[int, int]]) -> int:
    """How many of `heads` a mixer told `held` = (first, count) builds:
    its share of a layer that head-parallel chips divide; all of them
    where it is told nothing."""
    if held is None:
        return heads
    first, count = held
    if first < 0 or count < 1 or first + count > heads:
        raise ValueError(f"held heads {held} of {heads}")
    return count


def summed_over(axis_name: Optional[str], value):
    """`value` summed over the head-parallel chips (the named axis of the
    `shard_map` / `vmap` they run under) where there are any; where no
    axis is named, one chip runs alone and nothing is emitted."""
    return value if axis_name is None else jax.lax.psum(value, axis_name)


class WholeWidthNorm(nn.Module):
    """RMSNorm with ONE statistic over every column of a projection (all
    heads' together, OLMo 2's QK-norm) and a plain scale.  The statistic
    is not head-wise: under `axis_name` the sum of squares and the width
    are summed over the chips that share the heads (one number a token is
    what they exchange); with no axis it is the held columns' own mean."""

    eps: float
    dtype: jnp.dtype = jnp.float32
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        squares = summed_over(
            self.axis_name, jnp.sum(jnp.square(x), axis=-1, keepdims=True)
        )
        width = summed_over(self.axis_name, x.shape[-1])
        return (
            x * jax.lax.rsqrt(squares / width + self.eps) * scale
        ).astype(self.dtype)


class GroupedAttention(nn.Module):
    """`heads` query heads over `kv_heads` key/value heads, causal, the
    logits times `scale`.  As it stands: no positions, no band, no norms,
    no gate.  `qk_norm_eps` norms q and k a head (a zero-centred scale
    each, `q_norm` and `k_norm`) before `rope` turns a head's first
    columns, or, with `qk_norm_whole`, over ALL of the projection's
    columns at once under a plain scale (`WholeWidthNorm`, in the scope
    `qk_norm`); with `window`, query t sees the keys s with t - window < s
    <= t; with `query_gate` the q projection is twice as wide, a head's
    columns split q | gate, and the output is times sigmoid(gate), element
    by element.  With `held_heads` = (first, count) the layer builds only
    those query heads' columns of Wq (their K/V heads' of Wk and Wv) and
    rows of Wo and returns its part of the output, summed over `axis_name`
    where head-parallel chips run under one (`summed_over`)."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    scale: float
    dtype: jnp.dtype = jnp.float32
    trace_scope: str = "attn"
    qk_norm_eps: Optional[float] = None
    rope: Optional[Rope] = None
    query_gate: bool = False
    window: Optional[int] = None
    qk_norm_whole: bool = False
    held_heads: Optional[Tuple[int, int]] = None
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, dim = held_of(self.heads, self.held_heads), self.head_dim
        if heads * self.kv_heads % self.heads:
            raise ValueError("a share holds whole K/V heads")
        kv_heads = heads * self.kv_heads // self.heads
        with jax.named_scope(self.trace_scope):
            q, k, v = (
                dense(count * width, name, self.dtype, MIXER_IN)(x).reshape(
                    batch, length, count, width
                )
                for name, count, width in (
                    ("q", heads, dim * (1 + self.query_gate)),
                    ("k", kv_heads, dim), ("v", kv_heads, dim),
                )
            )
            if self.query_gate:
                q, gate = jnp.split(q, 2, axis=-1)
            if self.qk_norm_eps is not None and self.qk_norm_whole:
                with jax.named_scope("qk_norm"):
                    q, k = (
                        WholeWidthNorm(
                            self.qk_norm_eps, self.dtype, self.axis_name,
                            name=name,
                        )(t.reshape(batch, length, -1)).reshape(t.shape)
                        for name, t in (("q_norm", q), ("k_norm", k))
                    )
            elif self.qk_norm_eps is not None:
                q, k = (
                    RMSNorm(self.qk_norm_eps, self.dtype, True, name=name)(t)
                    for name, t in (("q_norm", q), ("k_norm", k))
                )
            if self.rope is not None:
                sow_rope_one_pass(self, self.rope.columns, q.shape, k.shape)
                q, k = partial_rotary(q, self.rope), partial_rotary(
                    k, self.rope
                )
            out = flash_attention.causal_attention(
                q, k, v, scale=self.scale, window=self.window
            )
            if self.query_gate:
                gate = jax.nn.sigmoid(gate.astype(jnp.float32))
                sow_step_metric(self, "query_gate_mean_ratio", gate.mean())
                out = (out * gate).astype(self.dtype)
            return summed_over(
                self.axis_name,
                dense(self.hidden, "o", self.dtype, MIXER_OUT)(
                    out.reshape(batch, length, heads * dim)
                ),
            )


class MoEFFN(nn.Module):
    """The shared expert, computed by every holder alike, plus this
    holder's part of the routed experts; `shared_experts` 0 builds no
    shared expert.  The shared expert is `shared_experts * expert_width`
    wide, or `shared_width` where the model gives it a width of its own;
    `form` is every expert's, routed and shared alike, `scores` the
    router's (`layers/moe.py: SCORES`); with `shared_gate` the shared
    expert's output is times one sigmoid a token of the layer's input.
    A call that gives `route_from` has the router read that tensor in x's
    place, under the named scope `route_scope` (`RoutedExperts`)."""

    hidden: int
    num_experts: int
    top_k: int
    expert_width: int
    shared_experts: int
    held_experts: Optional[Tuple[int, int]]
    routed_scaling: float
    bias_update_rate: float
    dtype: jnp.dtype = jnp.float32
    trace_scope: str = "glm/moe"
    renorm_eps: float = 0.0
    form: str = SWIGLU
    shared_width: Optional[int] = None
    scores: str = SIGMOID
    shared_gate: bool = False
    route_scope: str = "route"

    @nn.compact
    def __call__(self, x, route_from=None):
        with jax.named_scope(self.trace_scope):
            routed = RoutedExperts(
                num_experts=self.num_experts, top_k=self.top_k,
                ffn_dim=self.expert_width, held_experts=self.held_experts,
                routed_scaling=self.routed_scaling,
                bias_update_rate=self.bias_update_rate, dtype=self.dtype,
                renorm_eps=self.renorm_eps, form=self.form,
                scores=self.scores, route_scope=self.route_scope,
                name="routed",
            )(x, route_from)
            if not self.shared_experts:
                with jax.named_scope("combine"):
                    return routed.astype(self.dtype)
            with jax.named_scope("shared"):
                shared = MLP_OF_FORM[self.form](
                    self.hidden,
                    self.shared_width
                    or self.shared_experts * self.expert_width,
                    self.dtype, name="shared",
                )(x)
                if self.shared_gate:
                    # one number a token: sigmoid(x w_sg)
                    gate = jax.nn.sigmoid(
                        dense(1, "shared_gate", self.dtype)(x).astype(
                            jnp.float32
                        )
                    )
                    sow_step_metric(
                        self, "shared_gate_mean_ratio", gate.mean()
                    )
                    shared = gate * shared
            with jax.named_scope("combine"):
                return (
                    routed + shared.astype(jnp.float32)
                ).astype(self.dtype)


# ---- what a block's remat keeps, and how much of it the device can hold ----

# The KINDS of plain product whose output a rematerialised block may keep
# beside SAVED_NAMES (one `checkpoint_name` a kind; `dense` names them):
# a mixer's projections from the residual stream, MLA's two projections
# up from their latents, the mixer's out-projection, and the `gate_up` of
# the MLP or of the shared expert.  A block's LAST product (`down`) is
# read by no backward and is not rebuilt, so it carries no name, but in a
# block that norms the MLP's OUTPUT inside the residual branch: the
# norm's backward reads it, and `SwiGLU(down_kind=FFN_OUT)` names it
# there.  Nothing inside `layers/moe.py: routed_walk` is named (its
# `custom_vjp` keeps what it keeps); the routing AROUND it is, where
# `RoutedExperts` makes it, and is always kept (`moe.SAVED_NAMES`, in
# SAVED_NAMES above): a hundredth of one projection's bytes.
MIXER_IN, Q_UP, KV_UP, MIXER_OUT, GATE_UP, FFN_OUT = PRODUCT_NAMES = (
    "mixer_in", "mixer_q_up", "mixer_kv_up", "mixer_out", "gate_up",
    "ffn_out",
)

# `lean_step_bytes`: the share of the bytes a block's traced forward
# makes that the step holds at once through the block's backward, and
# the compiled step's own code on the device (79-265 MB in the cells)
# with whatever else the estimate does not see.  Both are FITTED, not
# derived: to the nine decoder cells' LEAN steps read on the chip after
# PR 60 took the log-sum-exp's padding out of them (`PERF.md` section 6,
# PR 60, has the row a cell: the estimate errs 0.26e9 high in the Kimi
# cell, which binds the share, and 0.37-1.97e9 in the others; the share
# was 0.34 beside 288 MiB while 2.4e9 of padding stood inside Laguna's).
BLOCK_SHARE = 0.25
PROGRAM_BYTES = 552 << 20


class Product(NamedTuple):
    """One named product of one block: keeping it holds `size` bytes
    from the forward to the block's backward, once for each time a step
    applies the block, and spares the rebuild `contraction` multiply-adds
    for each element kept."""

    name: str
    size: int
    contraction: int


class BlockShapes(NamedTuple):
    """What a block's traced forward tells the plan: its named products,
    the bytes of SAVED_NAMES, the bytes of every value it makes, what
    the chip's tiling adds to SAVED_NAMES (`tiled_bytes`), and the bytes
    of the block's weights cast to the type it computes in (0 where it
    computes in the type they are held in)."""

    products: Tuple[Product, ...]
    saved: int
    made: int
    padding: int = 0
    cast_weights: int = 0


def kept_products(
    blocks: Sequence[Sequence[Product]], budget: int, trips: int = 1
) -> Tuple[Tuple[str, ...], ...]:
    """For each block the names it keeps under `budget` bytes: the
    products in order of contraction width (the operations a kept byte
    buys), widest first, then of block index, each taken WHOLE if it
    still fits and passed over if not.  A stack applied `trips` times a
    step holds a kept product once a trip (one policy a block, whatever
    the trip), so it is kept only where `trips` of it fit."""
    order = sorted(
        (-product.contraction, index, place)
        for index, block in enumerate(blocks)
        for place, product in enumerate(block)
    )
    kept = [[] for _ in blocks]
    for _, index, place in order:
        product = blocks[index][place]
        if trips * product.size <= budget:
            budget -= trips * product.size
            kept[index].append(product.name)
    return tuple(tuple(names) for names in kept)


def _equations(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs its equations call,
    each beside the jaxpr that holds it; a kernel's body and a loop's
    are not entered (their values live a tile or a trip), and of a
    function with its own derivative only the function is."""
    for eqn in jaxpr.eqns:
        inner = [
            getattr(value, "jaxpr", value) for value in eqn.params.values()
            if hasattr(getattr(value, "jaxpr", value), "eqns")
        ]
        if eqn.primitive.name in ("pallas_call", "scan", "while", "cond"):
            inner = []
        if not inner:
            yield jaxpr, eqn
        for called in inner[:1]:
            yield from _equations(called)


def _bytes(variables) -> int:
    return sum(
        int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
        for v in variables if hasattr(v.aval, "shape")
    )


def tiled_bytes(variables) -> int:
    """`_bytes` as the chip lays the arrays out in HBM: the two minor
    axes in whole tiles of (32 bytes of rows, 128 columns), so that a
    float32 (B, heads, L, 1) column takes 128 times its values (what the
    streaming attention forward saved as its log-sum-exp until PR 60) and
    the (B, heads, 1, L) row it saves now 8 times them.  An array of ONE
    axis lies in whole tiles of 1,024 values (`s32[163840]{0:T(1024)}` in
    the compiled steps: the routing's flat arrays, `layers/moe.py`)."""
    total = 0
    for v in variables:
        if not hasattr(v.aval, "shape"):
            continue
        size = v.aval.dtype.itemsize
        if len(v.aval.shape) == 1:
            total += size * -(-v.aval.shape[0] // 1024) * 1024
            continue
        *lead, rows, columns = (1, 1) + tuple(v.aval.shape)
        tile = 32 // size
        total += size * int(np.prod(lead)) * (
            -(-rows // tile) * tile
        ) * (-(-columns // 128) * 128)
    return total


def _contraction(jaxpr, variable) -> int:
    """The width the product that made `variable` contracts."""
    for eqn in jaxpr.eqns:
        if variable in eqn.outvars and eqn.primitive.name == "dot_general":
            (contracted, _), _ = eqn.params["dimension_numbers"]
            shape = eqn.invars[0].aval.shape
            return int(np.prod([shape[axis] for axis in contracted]))
    return 0


@functools.lru_cache(maxsize=None)
def block_shapes(block: nn.Module, shape, dtype) -> BlockShapes:
    """`block`'s forward traced over an abstract (B, L, hidden) input: no
    array is made and nothing runs.  The products are the values `dense`
    named, at the bytes the trace gives them, so the plan follows the
    block's code and no count of it kept beside the code."""
    x = jax.ShapeDtypeStruct(shape, dtype)
    variables = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)
    # (the block is no layer of a model here: what it would sow for the
    # step's metrics is dropped, and a gauge set beside them stays unset)
    traced = jax.make_jaxpr(
        lambda v, x: block.apply(v, x, mutable=nn.DenyList(STEP_METRICS))
    )(variables, x)
    products, saved, made, padding = {}, 0, 0, 0
    for jaxpr, eqn in _equations(traced.jaxpr):
        made += _bytes(eqn.outvars)
        if eqn.primitive.name != "name":
            continue
        name, size = eqn.params["name"], _bytes(eqn.outvars)
        if name in SAVED_NAMES:
            saved += size
            padding += tiled_bytes(eqn.outvars) - size
        elif name in PRODUCT_NAMES:
            # one policy name keeps every product of its kind in the
            # block (q, k and v): they are one product to the plan
            before = products.get(name, Product(name, 0, 1 << 62))
            products[name] = Product(
                name, before.size + size,
                min(before.contraction, _contraction(jaxpr, eqn.invars[0])),
            )
    cast = 0 if dtype == jnp.float32 else sum(
        leaf.size for leaf in jax.tree.leaves(variables.get("params", {}))
    ) * jnp.dtype(dtype).itemsize
    return BlockShapes(
        tuple(products.values()), saved, made, padding, cast
    )


def held_trips(trips: int) -> int:
    """How many trips' worth of what one trip holds from its forward to
    its backward (saved inputs, SAVED_NAMES, kept products) a step holds
    at once: every trip's in the loop's stacks and, in a loop, one more,
    the trip in flight beside its slot in them.  The one looped cell's
    planned step on the chip holds 2.1e9 beside its lean step and what
    it keeps (`PERF.md` section 6, PR 60); this term is 1.05e9 of that
    and the rest stands inside `worker/trainer.py: DEVICE_SHARE`'s tenth,
    where PR 59 left it."""
    return trips + (trips > 1)


def lean_step_bytes(
    blocks: Sequence[BlockShapes], walks: Sequence[int], x_bytes: int,
    vocab: int, trips: int = 1,
) -> int:
    """A calibrated ESTIMATE (no bound) of what the step holds beside
    its state under the lean policy (nothing kept but SAVED_NAMES):
    every block's saved input (`x_bytes` each) and its SAVED_NAMES, the
    LARGEST block's working set, the cross-entropy's block of float32
    logits with its softmax and their gradient, and the program itself.
    A block's working set is `BLOCK_SHARE` of every value its traced
    forward makes and, in a routed block, what the walk's backward holds
    (`walks`, from `layers/moe.py: walk_bytes`).  A stack applied
    `trips` times a step holds every block's saved input and SAVED_NAMES
    once a TRIP, from that trip's forward to that trip's backward, and
    the trip in flight once more (`held_trips`); the working set, the
    cross-entropy's block and the program stand once.
    SAVED_NAMES count at the bytes the chip's tiling gives them (`saved +
    padding`, `tiled_bytes`) for EVERY application: derived, since PR 60
    no part of a fit (the attention core's log-sum-exp is a lane-major
    row, 8 times its values: 4.2 MB an application in the Ouro cell where
    the column it was took 67).  A loop over the stack also holds what a
    straight-line step does not: the blocks' weights cast to the compute
    type ONCE, ahead of the loop (the compiler hoists what no trip
    changes), and a trip's emitted state and the final norm's saved input
    (two `x_bytes` a trip).  The two constants are fitted so that it
    errs HIGH against each of the nine decoder cells' lean steps on the
    chip (`PERF.md` section 6, PR 60, has a row a cell; section 7 what a
    user sees where it errs low)."""
    return (
        held_trips(trips) * (
            len(blocks) * x_bytes
            + sum(block.saved + block.padding for block in blocks)
        )
        + (trips > 1) * (
            sum(block.cast_weights for block in blocks) + 2 * trips * x_bytes
        )
        + max(
            int(BLOCK_SHARE * block.made) + walk
            for block, walk in zip(blocks, walks)
        )
        + 3 * CE_BLOCK * vocab * 4
        + PROGRAM_BYTES
    )


def routed_walks(
    x, routed: Sequence[bool], top_k: int, expert_width: int,
    form: str = SWIGLU,
) -> Tuple[int, ...]:
    """For each block what its routed walk holds over `x` (B, L, hidden)
    (`layers/moe.py: walk_bytes`), 0 for a block that is not routed."""
    walk = walk_bytes(
        x.shape[0] * x.shape[1], x.shape[-1], top_k, expert_width,
        x.dtype.itemsize, form,
    )
    return tuple(walk if is_routed else 0 for is_routed in routed)


def remat_block(block_cls, kept: Sequence[str] = ()):
    """`block_cls` rebuilt in the backward but for the attention core's
    output and log-sum-exp, which stay from the forward (bfloat16 out and
    a float32 lane-major lse a layer: 34-268 MB of out and 4-34 MB of lse
    as the chip tiles it in the cells): the remat rebuilds
    the projections, the rotation and the norms, and the backward
    kernels read what the forward kernel wrote, so that kernel runs once
    a step (`ops/flash_attention.py: SAVED_NAMES`).  What the chunked
    scan of a linear-attention layer names joins the same policy
    (`ops/kda.py: SAVED_NAMES`, which says what it keeps and why), and so
    does what a routed block's backward reads of its routing (`layers/moe.py:
    SAVED_NAMES`: the scores, the picks, the picked scores, the sorted order
    and the group sizes, 19-36 MB a layer in the cells), so that the router,
    `top_k` and the sort run once a step.  A block with none of them in it
    saves nothing.  `kept` adds the names of the block's own products that
    `remat_blocks` found room for; empty, it is the policy above and nothing
    else.  One class a (block, names): the blocks of a model that keep the
    same names trace as one."""
    return _remat_class(block_cls, tuple(kept))


@functools.lru_cache(maxsize=None)
def _remat_class(block_cls, kept: Tuple[str, ...]):
    return nn.remat(
        block_cls,
        policy=jax.checkpoint_policies.save_only_these_names(
            *SAVED_NAMES, *kept
        ),
    )


def remat_blocks(
    block_cls, config, kinds, x, room: Optional[int], vocab: int,
    walks: Optional[Sequence[int]] = None, trips: int = 1,
):
    """The class to build each block `block_cls(config, kind)` of a model
    from, one a `kind`, where the blocks rematerialise over `x` (B, L,
    hidden).  `room` is the bytes the step's one device has free for the
    step, static data from the Trainer that placed the state
    (`worker/trainer.py: device_room`), and None in every trace that is
    no train step of a placed state (init, eval, predict, a program
    compiled ahead for another mesh): the lean policy then, and nothing
    is planned.  With a `room`, each distinct block is traced once
    (`block_shapes`), the lean step's estimate (`lean_step_bytes`;
    `walks` from `routed_walks` where blocks are routed) comes off the
    room, and `kept_products` spends what is left; the share of the named
    bytes kept is set in `worker_remat_kept_ratio`, on the host, as the
    step is traced.  `trips` is how many times a step applies the whole
    stack over one set of weights (a looped model's trips): everything a
    block holds from its forward to its backward is then held that many
    times."""
    if room is None:
        return [remat_block(block_cls)] * len(kinds)
    kept, ratio = [()] * len(kinds), 0.0
    if room > 0:
        shapes = [
            block_shapes(
                block_cls(config, kind, parent=None), x.shape, x.dtype
            )
            for kind in kinds
        ]
        lean = lean_step_bytes(
            shapes, walks or [0] * len(kinds),
            x.size * x.dtype.itemsize, vocab, trips,
        )
        products = [block.products for block in shapes]
        kept = kept_products(
            products, max(0, room - lean), held_trips(trips)
        )
        held = sum(
            p.size for block, names in zip(products, kept) for p in block
            if p.name in names
        )
        ratio = held / max(1, sum(p.size for block in products for p in block))
    remat_kept_ratio.set(ratio)
    return [remat_block(block_cls, names) for names in kept]


def _ce_blocks(block: int, *rows):
    """Each (tokens, ...) array as (tokens // block, block, ...)."""
    return tuple(
        a.reshape((a.shape[0] // block, block) + a.shape[1:]) for a in rows
    )


def _block_nll(h_block, kernel, t_block):
    """A block's negative log-likelihoods from its float32 logits (the
    operands in the compute type), and the two parts of its softmax,
    exp(logits - the row's max) and its sum over the row."""
    logits = jnp.dot(h_block, kernel, preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, t_block[:, None], axis=1)[:, 0]
    highest = jnp.max(logits, axis=-1)
    raised = jnp.exp(logits - highest[:, None])
    total = jnp.sum(raised, axis=-1)
    return jnp.log(total) + highest - picked, raised, total


def _ce_block_grads(h_block, t_block, c_block, kernel, dtype):
    """One block's (nll (block,), d h (block, hidden), d head (hidden,
    vocab)), float32, for the rows' cotangents `c_block`: its logits made
    once, then the gradient's two products, with the operand types JAX's
    transposed product has (the float32 `delta` against the compute-type
    kernel and states); `delta` reads the exponentials the log-sum-exp
    made, as the transposed log-sum-exp does."""
    # the block is sliced ONCE for its two products: fused into each, the
    # slice cost the head's gradient a third more on the chip (PERF.md)
    h_block = jax.lax.optimization_barrier(h_block.astype(dtype))
    nll, raised, total = _block_nll(h_block, kernel, t_block)
    columns = jnp.arange(kernel.shape[1], dtype=t_block.dtype)
    delta = (c_block / total)[:, None] * raised - jnp.where(
        columns == t_block[:, None], c_block[:, None], 0.0
    )
    return nll, jax.lax.dot_general(
        delta, kernel, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ), jax.lax.dot_general(
        h_block, delta, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _ce_grad_loop(h, head_kernel, targets, cotangents, dtype, block):
    """ONE loop over the blocks, three vocabulary-wide products a block
    (`_ce_block_grads`): (nll (tokens,), d h (tokens, hidden) float32,
    d head (hidden, vocab) float32 summed over the blocks)."""
    kernel = head_kernel.astype(dtype)

    def one(d_head, args):
        nll, d_h, more = _ce_block_grads(*args, kernel, dtype)
        return d_head + more, (nll, d_h)

    d_head, (nll, d_h) = jax.lax.scan(
        one, jnp.zeros(kernel.shape, jnp.float32),
        _ce_blocks(block, h, targets, cotangents),
    )
    return nll.reshape(-1), d_h.reshape(h.shape), d_head


def blocks_again(uniform, blocks: int):
    """How many of the `blocks` the backward makes again: none where the
    rows' cotangent was `uniform`, the one the forward reckoned with."""
    return jnp.where(uniform, 0, blocks)


def _ce_grads_again(
    again, saved, h, head_kernel, targets, cotangents, dtype, block,
):
    """`saved` (d h, d head) with the first `again` blocks' share made
    anew for `cotangents`: all of them or none, so the loop runs whole or
    not at all, in place either way."""
    kernel = head_kernel.astype(dtype)
    rows = _ce_blocks(block, h, targets, cotangents)

    def one(i, grads):
        d_h, d_head = grads
        _, d_h_block, more = _ce_block_grads(
            *(a[i] for a in rows), kernel, dtype
        )
        return d_h.at[i].set(d_h_block), jnp.where(i == 0, 0.0, d_head) + more

    d_h, d_head = saved
    d_h, d_head = jax.lax.fori_loop(
        0, again, one, (d_h.reshape(rows[0].shape), d_head)
    )
    return d_h.reshape(h.shape), d_head


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighed_nll(h, head_kernel, targets, weights, dtype, block):
    kernel = head_kernel.astype(dtype)
    nll = jax.lax.map(
        lambda args: _block_nll(args[0].astype(dtype), kernel, args[1])[0],
        _ce_blocks(block, h, targets),
    ).reshape(-1)
    return weights * nll, nll


def _weighed_nll_fwd(h, head_kernel, targets, weights, dtype, block):
    nll, d_h, d_head = _ce_grad_loop(
        h, head_kernel, targets, weights, dtype, block
    )
    return (weights * nll, nll), (
        h, head_kernel, targets, weights, nll, d_h, d_head
    )


def _weighed_nll_bwd(dtype, block, saved, cotangents):
    h, head_kernel, targets, weights, nll, d_h, d_head = saved
    g_weighed, g_nll = cotangents
    # The forward took the rows' cotangent to be `weights` times ONE
    # scalar, which is what a mean of the weighed losses sends down; that
    # is tested here, on the device, and anything else gets the loop again
    # with the cotangent it did send.
    counted = weights != 0
    scalar = jnp.max(jnp.where(counted, g_weighed, -jnp.inf))
    scalar = jnp.where(counted.any(), scalar, 0.0)
    uniform = jnp.all(jnp.where(counted, g_weighed == scalar, True)) & (
        jnp.all(g_nll == 0)
    )
    d_h, d_head = _ce_grads_again(
        blocks_again(uniform, h.shape[0] // block), (d_h, d_head), h,
        head_kernel, targets, g_weighed * weights + g_nll, dtype, block,
    )
    scalar = jnp.where(uniform, scalar, 1.0)
    return (
        (scalar * d_h).astype(h.dtype),
        (scalar * d_head).astype(head_kernel.dtype),
        None,
        g_weighed * nll,
    )


_weighed_nll.defvjp(_weighed_nll_fwd, _weighed_nll_bwd)


def blocked_nll(
    h, head_kernel, targets, dtype, block: int = CE_BLOCK, weights=None,
):
    """(`weights` * nll, nll), both (tokens,) float32: the negative
    log-likelihood of `targets` under softmax(h @ head_kernel), `block`
    tokens' logits at a time, nothing (tokens, vocab)-shaped ever held.
    `weights` (tokens,) float32, ones where absent, are what the loss
    multiplies a row by, and they go IN: differentiated, the forward makes
    a block's logits ONCE and takes the losses AND the gradient's two
    products from them (`_ce_grad_loop`; the head's gradient sums over the
    blocks in its own float32), which needs a row's cotangent before the
    backward runs.  The backward scales what was saved by the one scalar
    that a mean of the weighed losses sends down, in float32 and before
    the states' gradient is rounded to their type; any other cotangent, on
    either output, gets the exact gradient from a second loop.  The
    gradient to `weights` is the cotangent times the loss, always.
    Undifferentiated (eval, predict, `init`) it is one product a block."""
    tokens = h.shape[0]
    if tokens % block:
        block = tokens
    if weights is None:
        weights = jnp.ones((tokens,), jnp.float32)
    return _weighed_nll(
        h, head_kernel, targets, weights.astype(jnp.float32), dtype, block
    )


def weighed_nll(
    h, head_kernel, ids, shift: int, dtype, scope: str, weights=None,
):
    """(`weights` * nll, nll), both (B, L - shift): the per-position loss
    of h (B, L, d) against the ids `shift` places on; the positions with
    no such id are left out (they ride through the blocks at weight 0).
    `h` may stack several states, (S, B, L, d), each read against the same
    ids: (S, B, L - shift) then, as `weights` is where given, from ONE
    blocked pass over all S x B x L rows, so that the head's gradient sums
    over every state's blocks in one loop."""
    batch, length = ids.shape
    stacked = h.shape[:-3]
    shape = stacked + (batch, length)
    targets = jnp.roll(ids, -shift, axis=1)
    if weights is None:
        weights = jnp.ones(shape[:-1] + (length - shift,), jnp.float32)
    weights = jnp.pad(
        weights.astype(jnp.float32), [(0, 0)] * (len(shape) - 1) + [(0, shift)]
    )
    with jax.named_scope(scope):
        weighed, nll = blocked_nll(
            h.reshape(-1, h.shape[-1]), head_kernel,
            jnp.broadcast_to(targets, shape).reshape(-1), dtype,
            weights=weights.reshape(-1),
        )
    return tuple(
        out.reshape(shape)[..., :length - shift] for out in (weighed, nll)
    )


def shifted_nll(h, head_kernel, ids, shift: int, dtype, scope: str):
    """`weighed_nll` at a weight of 1 on every position kept."""
    return weighed_nll(h, head_kernel, ids, shift, dtype, scope)[0]


# ---- the zoo functions of a next-token model ------------------------------


def loss(labels, predictions):
    """`predictions` are the model's per-position negative
    log-likelihoods of the next token; the record's label byte is not
    used."""
    return predictions.mean()


def optimizer(lr: float = 1e-4):
    return optax.adam(lr)


def eval_metrics_fn():
    return {
        "perplexity": lambda labels, predictions: float(
            np.exp(np.mean(predictions))
        ),
    }


def param_sharding(path, value):
    """Expert stacks over `expert`, the token embedding over `model`;
    everything else replicated."""
    spec = moe_param_sharding(path, value)
    if spec is not None:
        return spec
    return embedding_param_sharding(path, value)
