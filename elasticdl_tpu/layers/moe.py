"""Mixture-of-Experts layer with expert parallelism over the mesh
`expert` axis.

Net-new capability relative to the reference (SURVEY.md §2: upstream has
NO expert parallelism), completing the framework's fourth mesh axis.
Design follows the GShard/Switch dense-dispatch recipe, expressed the
pjit way (SURVEY.md §7: annotate shardings, let XLA insert collectives):

- the router computes top-1 gates per token; dispatch/combine are DENSE
  one-hot tensors (tokens, experts, capacity) built with static shapes —
  no sorting, no dynamic shapes, nothing the TPU can't tile;
- expert weights are stacked as (experts, ...) arrays whose leading dim
  is sharded `P("expert", ...)` (see `moe_param_sharding`); the dispatch
  einsum then contracts a token-sharded operand against an
  expert-sharded one, and the XLA SPMD partitioner emits the all-to-all
  over ICI that hand-written MoE frameworks schedule manually;
- fixed expert capacity (capacity_factor * tokens / experts) bounds
  memory; overflowing tokens fall through the residual connection
  (standard Switch semantics — the layer returns gate-weighted expert
  output, zeros for dropped tokens, so callers add the residual).

Capacity assignment uses the standard position-in-expert cumsum, which
is deterministic and position-biased (earlier tokens win slots), exactly
like the reference implementations.

`RoutedExperts` is the second scheme, for decoders of the DeepSeek-V3 /
GLM family: sigmoid scores, a selection bias that picks and does not
weigh, top-k of ALL experts, and a layer that is told which experts it
holds (`held_experts`) and computes their part of the result.  One-hot
dispatch cannot stand there (16,384 tokens x 64 experts x capacity), so
the slots routed to held experts are SORTED by expert into one buffer of
static worst-case size (tokens x top_k rows), the group sizes travel as
data, and the buffer is WALKED, `CHUNK` rows a trip, only as far as its
last live row (`routed_walk`): the gather, the grouped products
(`grouped_matmul`), the expert's activation (its form, `FORMS`, is an
argument of the walk: gated SwiGLU or ReGLU over a fused gate-and-up
stack, or a squared ReLU over an up stack alone), the slot weights and the
scatter-add all cost what the rows routed here cost, rounded up to a chunk,
forward
and backward.  What still follows the worst case is index arithmetic: the
sort, one int32 / float32 entry a slot, and the backward's four zeroed
buffers.  No token is dropped at any imbalance and nothing recompiles
when the loads change.  Both layers count router load with
`expert_loads`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import (
    AUX_LOSS,
    STEP_METRICS,
    sow_step_metric,
)


# Holds buffers a layer updates itself (no gradient); the two collections
# the Trainer reads are layers/step_metrics.py's.
ROUTER_STATE = "router_state"

# What a routed expert layer sows into STEP_METRICS (`RoutedExperts`),
# read once a task with the loss: leaf name -> gauge by layer.
step_metrics.declare(
    "expert_load_imbalance_ratio",
    metrics_lib.default_registry().gauge(
        "worker_moe_expert_load_imbalance_ratio",
        "largest router load over the mean load, over all the router's "
        "outputs, last step of the task",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "routed_here_ratio",
    metrics_lib.default_registry().gauge(
        "worker_moe_routed_here_ratio",
        "routing slots that chose an expert held here / tokens x top_k, "
        "last step of the task",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "live_chunks_ratio",
    metrics_lib.default_registry().gauge(
        "worker_moe_live_chunks_ratio",
        "chunks of the sorted buffer the layer walked / chunks of its "
        "worst case (layers/moe.py: routed_walk), last step of the task; "
        "1.0 means the walk saved nothing",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "dropped_tokens",
    metrics_lib.default_registry().counter(
        "worker_moe_dropped_tokens_total",
        "slots routed to a held expert that got no row (sorted dispatch "
        "has a worst-case buffer: stays 0)",
    ),
)
# A constant of the layer's shapes and `TILE`, known as the layer is traced:
# set there, on the host (as `worker_remat_kept_ratio` is), and never sown,
# so no step carries it and no fetch reads it.
padded_work_ratio = metrics_lib.default_registry().gauge(
    "worker_moe_padded_work_ratio",
    "multiply-adds of the grouped products at the widths the walk pads "
    "them to (layers/moe.py: TILE) / at the layer's own widths, minus "
    "1; 0.0 means nothing is padded",
    labelnames=("layer",),
)


# What a block's backward reads of `RoutedExperts`' routing, NAMED where the
# layer makes it (`checkpoint_name`): the scores (n, experts) float32 (under
# a softmax their exponentials, `_softmax`), which the backward of the score
# function and of the router's product reads; the picks and the picked
# scores, one a slot; the slots' sorted order; the held groups' sizes.  A
# remat that keeps them (`model_zoo/common/decoder.py: SAVED_NAMES`, always)
# rebuilds no router product, no sigmoid or exponential, no `top_k`, no
# gather of the picked scores, no count of the loads and no sort; what it
# still rebuilds of the routing is sums and a division: a slot's weight from
# its picked score and, under a softmax, the exponentials' sum.  The three
# that are one value a slot are named as the FLAT (tokens x top_k,) view the
# walk takes of them: the chip tiles the minor axis of an (n, top_k) array to
# 128 lanes, 8.4 MB held for 0.66 MB of values at 16,384 x 10 (as the
# attention core's log-sum-exp column was until it went lane-major).  Outside
# a remat a name is the identity and lowers to nothing.
SCORES_NAME, PICKS_NAME, PICKED_NAME, ORDER_NAME, SIZES_NAME = SAVED_NAMES = (
    "router_scores", "router_picks", "router_picked_scores",
    "routed_slot_order", "routed_group_sizes",
)


def _named_flat(x, name: str):
    """`x` (n, top_k), its FLAT view carrying `name`: what a remat that
    saves the name holds is the (n x top_k,) array, and the (n, top_k) one is
    a view of it."""
    return checkpoint_name(x.reshape(-1), name).reshape(x.shape)


def expert_loads(expert_idx, num_experts: int):
    """(num_experts,) f32: how many routing slots chose each expert."""
    return jnp.zeros((num_experts,), jnp.float32).at[
        expert_idx.reshape(-1)
    ].add(1.0)


def grouped_matmul(lhs, rhs, group_sizes):
    """(rows, k) x (groups, k, n) -> (rows, n): rows [0, g0) times rhs[0],
    the next g1 rows times rhs[1], ...; rows past sum(group_sizes) come
    back zero.  The device does work for the rows in groups only, and
    on the TPU leaves the other rows of its result UNWRITTEN, forward
    and in both transposes: masked here on the way in and on the way
    out, so neither the product nor its gradients ever read them."""
    live = (jnp.arange(lhs.shape[0]) < group_sizes.sum())[:, None]
    return jnp.where(
        live,
        jax.lax.ragged_dot(jnp.where(live, lhs, 0), rhs, group_sizes),
        0,
    )


# Rows of the sorted buffer one trip of `routed_walk` works on.  Placed by
# PR 34's chip probe (v5e; one layer, forward + backward under remat, ms,
# whole-buffer form | CHUNK 4,096 / 8,192 / 16,384 / 32,768 with the
# stacks' transposes still inside the loop) at 16,384 tokens x 2,048:
# top-4, 8 held of 64, width 1,536 (65,536 slots) with an eighth of the
# slots here 42 | 24 / 23 / 26 / 34, a quarter 46 | 35 / 32 / 31 / 38,
# all 72 | 101 / 87 / 82 / 79; top-8, 32 held of 256, width 512 (131,072
# slots) 67 | 41 / 36 / 34 / 39, 70 | 63 / 53 / 53 / 56, 88 | 165 / 124
# / 110 / 100.  A trip pays ~0.5 ms for each scatter-add into the
# (tokens, hidden) float32 carry whatever its rows (and 87 ns a row), so
# small chunks lose at full load what they win at low load; as committed
# 16,384 reads 24 / 29 / 54 / 79 and 29 / 47 / 67 / 100 at an eighth /
# a quarter / a half / all (PERF.md section 6).
# What a trip's scatter-add pays hangs on the carry's WIDTH and on nothing
# else (PR 58's probe, `scripts/probe_routed_walk.py`: v5e, one layer alone,
# forward + backward, three traced calls; ms a chunk of 16,384 rows, a pass,
# the same whether the held experts' loads are even, each group a pass over
# all the tokens, or one expert holds nine tenths of the rows as one run):
#   columns   1,536  2,048  2,304  2,560  2,688  3,072  4,096
#   ms        1.55   2.08   3.01   8.00   2.44   3.12   3.37
# 2,560 columns pay 8.0 ms where their neighbours pay 2.4-3.0 (1.3 us a live
# row at 12,288 live rows a chunk): the carry of such tokens is a lane tile
# wider (`_carry_width`, `SLOW_SCATTER_WIDTHS`) and cut to them after the
# walk.  Summing a token's k rows from the TOKEN's side instead (a trip
# writes its rows to a (slots, hidden) buffer; top_k gathers of a row a
# token and one fused add after the loop) was built and measured: its cost
# follows the slots, 0.66-0.70 ms a gather of 16,384 rows of 2,560 bfloat16
# columns, ~6 ms a pass at 98,304 slots whatever the load.  The walk a call,
# scatter-add a trip | from the token's side, at 0.125 / 0.25 / 0.5 of the
# slots here:
#   16,384 x 2,560, top-6  (98,304 slots)  27.98 / 51.88 / 79.85 | 22.40 / 30.53 / 42.91
#   16,384 x 2,048, top-8  (131,072)       14.51 / 24.71 / 44.93 | 21.68 / 28.17 / 40.79
#   16,384 x 2,048, top-10 (163,840)       25.14 / 35.27 / 55.56 | 32.35 / 38.72 / 51.51
#   16,384 x 2,304, top-8  (131,072)       24.44 /   -   / 77.67 | 31.19 / 43.09 / 66.89
#   16,384 x 2,688, top-6  (98,304)        25.34 /   -   / 73.61 | 32.38 / 46.83 / 71.26
# It loses 6-7 ms a call at one live chunk at every width but the slow one
# and wins only past three; at the slow one the wider carry beats it (PERF.md
# section 6, PR 58).
CHUNK = 16384
# every index of the walk is a token's, in bounds by construction
_PIB = "promise_in_bounds"

# What a width of a grouped product is padded up to a whole multiple of
# (`_padded`).  The chip's `ragged_dot` kernel tiles each of (rows,
# contraction, columns) with the largest of 512 / 256 / 128 that DIVIDES
# it (the compiled text says which: `ragged_dot_tiling="512,128,128"` at
# Nemotron's 2,688 x 1,856, `512,512,512` at GLM's 2,048 x 1,536,
# `512,256,512` at Kimi's 2,304 x 1,024, the same for rows x stack, rows
# x transposed stack and the stacks' gradients), and a 128-wide tile runs
# at a third of the pace of a 256-wide one.  Placed by PR 51's chip probe
# (v5e; Nemotron's layer alone, 16,384 tokens of 2,688, top-6, 8 held of
# 128, `relu2`, forward + backward under remat, three traced calls; ms,
# at 0.0625 / 0.2 / 0.65 of the slots here: the eight `ragged-dot`s [of
# them rows x up-shaped stack | rows x down-shaped stack | the two stack
# gradients at 0.2], the whole walk):
#   ffn x hidden  (tile)   products               [at 0.2]            walk
#   1,856 x 2,688 (none)   18.44 / 44.01 / 129.67 [12.6 | 13.8 | 17.6] 29.4 / 61.0 / 158.9
#   1,920 x 2,688 (128)    18.41 / 43.98 / 129.69 [12.6 | 13.8 | 17.6] 29.8 / 61.0 / 157.9
#   2,048 x 2,688          8.46 / 20.22 / 59.39   [5.2 | 7.4 | 7.7]    20.0 / 37.3 / 87.6
#   1,920 x 2,816          12.32 / 29.39 / 86.53  [9.7 | 8.4 | 11.3]   24.6 / 47.6 / 116.4
#   2,048 x 2,816 (256)    6.03 / 14.33 / 42.01   [4.2 | 5.0 | 5.1]    18.2 / 32.4 / 71.8
#   2,048 x 3,072 (512)    5.09 / 12.00 / 35.11   [4.0 | 4.0 | 4.0]    17.7 / 30.6 / 65.7
# (GLM's 2,048 x 1,536 gated layer, top-4, unpadded: 2.96 / 6.44 / 17.70,
# 12.0 / 15.4 / 37.4).  A whole lane tile alone (1,920) buys nothing; all
# three kinds of product want the same shapes; over the layer's own
# multiply-adds the products read 10-15% of the MXU's peak unpadded,
# 31-46% at 256, 37-55% at 512 (GLM's 40-69%).  512 is 6-9% better on
# this walk at the two higher loads but would pad Kimi's 2,304 to 2,560,
# where the same probe reads the walk 13.5 / 38.7 / 63.0 unpadded and
# 15.8 / 39.6 / 62.6 padded (0.03 / 0.3 / 0.55 of its slots): its
# products gain 6-9% and the pads and wider rows give it back.  At 256
# every sibling's widths are whole and its program the unpadded one
# (PERF.md section 6, PR 51).
TILE = 256


def _whole(dim: int) -> int:
    """`dim` rounded up to a whole number of `TILE`s."""
    return -(-dim // TILE) * TILE


def padded_work(hidden: int, ffn_dim: int) -> float:
    """Multiply-adds of an expert's products at the padded widths over
    those at its own, minus 1 (both products are hidden x ffn a row)."""
    return _whole(hidden) * _whole(ffn_dim) / (hidden * ffn_dim) - 1.0


def _zeros_to(x, shape):
    """`x` with zeros after it up to `shape`; `x` itself where it has it."""
    if x.shape == tuple(shape):
        return x
    return jnp.pad(x, [(0, to - n) for n, to in zip(x.shape, shape)])


def _cut_to(x, shape):
    """The leading `shape` of `x`; `x` itself where it has it."""
    if x.shape == tuple(shape):
        return x
    return x[tuple(slice(0, n) for n in shape)]


def _first_as(w, parts: int, hidden: int, ffn: int, fit):
    """A first stack, or its gradient, as (experts, hidden, parts x ffn),
    EACH of its `parts` (`swiglu`'s gate and up, which the activation
    splits apart) brought to `ffn` columns on its own by `fit` (`_zeros_to`
    or `_cut_to`); `w` itself where it has that shape."""
    if w.shape[1:] == (hidden, parts * ffn):
        return w
    by_part = w.reshape(*w.shape[:2], parts, -1)
    return fit(by_part, (w.shape[0], hidden, parts, ffn)).reshape(
        w.shape[0], hidden, parts * ffn
    )


def _swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return nn.silu(gate) * up


def _relu2(up):
    return jnp.square(nn.relu(up))


def _reglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return nn.relu(gate) * up


# An expert is (first stack, activation, second stack).  Its form names the
# activation: -> (the first stack's leaf, that stack's width in `ffn_dim`s,
# the activation from its output to the second stack's input).
SWIGLU, RELU2, REGLU = "swiglu", "relu2", "reglu"
FORMS = {
    SWIGLU: ("expert_w_gate_up", 2, _swiglu),   # (silu(x Wg) * (x Wu)) Wd
    RELU2: ("expert_w_up", 1, _relu2),          # (relu(x Wu))^2 Wd: no gate
    REGLU: ("expert_w_gate_up", 2, _reglu),     # (relu(x Wg) * (x Wu)) Wd
}


def _kept(fn, slope):
    """Elementwise `fn` with its output carrying `SCORES_NAME` and its
    derivative READ OFF THE NAMED OUTPUT: `slope(fn(x))` is d fn / dx as
    jax's own rule for `fn` writes it.  That rule reads the output where the
    primitive made it, ahead of any name, so a remat that saved the name
    alone would still rebuild the router's product and `fn` to have that
    value again.  (The rule names the output ITSELF: called through the
    `custom_jvp` there, the name would lie inside a `custom_jvp_call`
    equation, where a remat's policy does not look.)"""
    def named(x):
        return checkpoint_name(fn(x), SCORES_NAME)

    kept = jax.custom_jvp(named)

    @kept.defjvp
    def kept_jvp(primals, tangents):
        out = named(*primals)
        return out, tangents[0] * slope(out)

    return kept


_kept_exp = _kept(jnp.exp, lambda raised: raised)


def _softmax(logits):
    """`jax.nn.softmax` over the last axis as it is differentiated here
    (through its exponentials and their sum, the row's maximum held
    constant), the exponentials kept (`_kept`): they are what the backward
    reads, the scores themselves it does not."""
    raised = _kept_exp(
        logits - lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    )
    return raised / raised.sum(axis=-1, keepdims=True)


# The router's scores over its float32 logits (n, num_experts), chosen
# like `FORMS`: each expert's own sigmoid, or one softmax over all.  Each
# keeps, under `SCORES_NAME`, the one (n, num_experts) array its backward
# reads: the sigmoid its scores, the softmax its exponentials.
SIGMOID, SOFTMAX = "sigmoid", "softmax"
SCORES = {
    SIGMOID: _kept(jax.nn.sigmoid, lambda s: s * (1 - s)),
    SOFTMAX: _softmax,
}


# The widths of a (tokens, width) float32 carry at which the chip's
# scatter-add of a chunk's rows runs at a quarter of its neighbours' pace
# (the table above `CHUNK`).  Only what a cell has run is listed: the
# widths read past 4,096 columns are worse (33 ms a chunk at 5,120, 93 at
# 5,248, 18 at 7,168) and a lane tile up is no cure there; two carries of
# half the width are (PERF.md section 7 (52)).
SLOW_SCATTER_WIDTHS = frozenset({2560})


def _carry_width(hidden: int) -> int:
    """The width of the float32 (tokens, width) carries a trip scatter-adds
    into: `hidden`, or the next lane tile (128) up that is not a width the
    chip scatters to slowly."""
    while hidden in SLOW_SCATTER_WIDTHS:
        hidden += 128
    return hidden


def _chunks(slots: int):
    """(rows a trip, trips over the whole worst-case buffer)."""
    chunk = min(CHUNK, slots)
    return chunk, -(-slots // chunk)


def _trips(rows, chunk: int):
    """Chunks that hold the buffer's first `rows` rows."""
    return (rows + chunk - 1) // chunk


def walk_bytes(
    tokens: int, hidden: int, top_k: int, ffn_dim: int, itemsize: int,
    form: str = SWIGLU,
) -> int:
    """What `routed_walk`'s backward holds at once, from its own shapes:
    the four (slots, width) buffers it fills for the stacks' gradients
    (the rows, the first stack's output's gradient at the form's width,
    the activation, the output's gradient; each at the width the products
    are padded to, `TILE`), three float32 sums of a row a token (the
    forward's, its cotangent, d_tokens; at the carry's width,
    `_carry_width`) and one chunk's rows in flight, values and
    gradients."""
    chunk, total = _chunks(tokens * top_k)
    widths = 2 * _whole(hidden) + (FORMS[form][1] + 1) * _whole(ffn_dim)
    return (
        (total + 2) * chunk * widths * itemsize
        + 3 * tokens * _carry_width(hidden) * 4
    )


def _chunk_of(c, chunk, top_k, order, weights, group_sizes):
    """Chunk `c` of the sorted buffer: (the routing slot of each of its
    rows, that slot's token, its weight, how many of the chunk's rows
    each group owns)."""
    ends = jnp.cumsum(group_sizes)
    into = lambda at: jnp.clip(at - c * chunk, 0, chunk)  # noqa: E731
    slot = lax.dynamic_slice(order, (c * chunk,), (chunk,))
    return (
        slot, slot // top_k, weights.at[slot].get(mode=_PIB)[:, None],
        into(ends) - into(ends - group_sizes),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def routed_walk(tokens, w_first, w_down, order, weights, group_sizes,
                form=SWIGLU):
    """sum over the sorted buffer's rows r < sum(group_sizes) of
    weights[order[r]] * Expert_{group of r}(tokens[order[r] // top_k]),
    scattered to that token: (n, hidden) float32; an expert is act(x
    w_first) w_down, `form` naming the activation and with it the first
    stack's width (`FORMS`).

    The buffer (`order`: the routing slots, token-major, sorted by group,
    the slots of no group last; `weights`: one a slot, unsorted) is walked
    CHUNK rows a trip for ceil(rows / CHUNK) trips, a count the DEVICE
    reads from `group_sizes`: a chunk past the last live row is neither
    gathered, multiplied, activated, weighted nor scattered, forward or
    backward, and one program serves every load.  A chunk's dead tail
    (the last live chunk's) is masked by `grouped_matmul`.  The float32
    carries a trip scatter-adds into (the sum, and the tokens' gradient in
    the backward) are `_carry_width(hidden)` wide, zeros past `hidden`, and
    cut to the tokens after the walk: the chip scatters to some widths at a
    quarter of the pace of their neighbours.  A dynamic trip
    count has no reverse-mode rule, hence the hand-written backward below:
    the same chunks, each one's forward rebuilt (the residuals are the
    arguments, so a rematerialised block's second forward is dead code)
    and its inputs to the stacks' gradients written to buffers that are
    zero where no trip went; the two stack gradients are ONE ragged
    product each after the walk, whose cost follows the rows.  Every
    grouped product runs at widths padded with zeros to whole `TILE`s
    (`_padded`: the tokens and the stacks once a walk, the buffers
    allocated so), and what the padding got is cut off before a value or
    a gradient leaves: the arguments and the results keep their shapes.
    """
    return _walk(tokens, w_first, w_down, order, weights, group_sizes, form)


def _padded(tokens, w_first, w_down, form):
    """(tokens, the two stacks) with zeros up to whole `TILE`s along the
    hidden and the expert's width: the shapes the grouped products run at.
    Zero columns of the first stack give zero columns of its output, which
    the activation keeps zero (relu(0)^2 = 0, silu(0) * 0 = 0, relu(0) * 0
    = 0) and which meet zero rows of the second; zero columns of the tokens
    meet zero rows of the first stack.  Each is the argument itself where
    its
    dimensions are whole already."""
    hidden, ffn = _whole(tokens.shape[1]), _whole(w_down.shape[1])
    return (
        _zeros_to(tokens, (tokens.shape[0], hidden)),
        _first_as(w_first, FORMS[form][1], hidden, ffn, _zeros_to),
        _zeros_to(w_down, (w_down.shape[0], ffn, hidden)),
    )


def _walk(tokens, w_first, w_down, order, weights, group_sizes, form):
    activation = FORMS[form][2]
    slots = order.shape[0]
    hidden = tokens.shape[1]
    top_k = slots // tokens.shape[0]
    chunk, total = _chunks(slots)
    carry_w = _carry_width(hidden)
    with jax.named_scope("dispatch"):
        order = jnp.pad(order, (0, total * chunk - slots))
    # once a walk, outside the loop: the pads are the products' cost
    with jax.named_scope("experts"):
        wide, w_first, w_down = _padded(tokens, w_first, w_down, form)

    def trip(c, out):
        with jax.named_scope("dispatch"):
            _, at, weight, sizes = _chunk_of(
                c, chunk, top_k, order, weights, group_sizes
            )
            rows = wide.at[at].get(mode=_PIB)
        with jax.named_scope("experts"):
            expert_out = _cut_to(grouped_matmul(
                activation(grouped_matmul(rows, w_first, sizes)),
                w_down, sizes,
            ), (chunk, hidden))
        with jax.named_scope("combine"):
            # (padded as bfloat16: half the bytes of the float32 products)
            return out.at[at].add(
                _zeros_to(expert_out, (chunk, carry_w)).astype(jnp.float32)
                * weight, mode=_PIB,
            )

    # the loop itself is `combine`'s: its carry is the sum
    with jax.named_scope("combine"):
        return _cut_to(lax.fori_loop(
            0, _trips(group_sizes.sum(), chunk), trip,
            jnp.zeros((tokens.shape[0], carry_w), jnp.float32),
        ), tokens.shape)


def _walk_fwd(*args):
    return _walk(*args), args[:6]


def _walk_bwd(form, args, g):
    tokens, w_first, w_down, order, weights, group_sizes = args
    parts, activation = FORMS[form][1:]
    slots = order.shape[0]
    hidden, ffn = tokens.shape[1], w_down.shape[1]
    top_k = slots // tokens.shape[0]
    chunk, total = _chunks(slots)
    carry_w = _carry_width(hidden)
    # the padding's rows are dead; its slot 0 repeats, so the weights'
    # gradient may promise distinct slots only where nothing is padded
    padded = total * chunk - slots
    with jax.named_scope("dispatch"):
        order = jnp.pad(order, (0, padded))
    dtype = tokens.dtype
    # padded (`_padded`) and transposed once, outside the loop (inside it
    # they are two copies of the stacks a trip)
    with jax.named_scope("experts"):
        wide, w_first, w_down = _padded(tokens, w_first, w_down, form)
        w_first_t, w_down_t = (
            jnp.swapaxes(w, 1, 2) for w in (w_first, w_down)
        )

    def trip(c, carry):
        d_tokens, d_weights, saved = carry
        with jax.named_scope("dispatch"):
            slot, at, weight, sizes = _chunk_of(
                c, chunk, top_k, order, weights, group_sizes
            )
            rows = wide.at[at].get(mode=_PIB)
            g_rows = g.at[at].get(mode=_PIB)
        with jax.named_scope("experts"):
            # a product's transpose to its rows is the product with the
            # stack transposed, under the same masks
            first = grouped_matmul(rows, w_first, sizes)
            act, pull_first = jax.vjp(activation, first)
            expert_out = _cut_to(
                grouped_matmul(act, w_down, sizes), (chunk, hidden)
            )
            d_out = _zeros_to(
                (g_rows * weight).astype(dtype), rows.shape
            )
            (d_first,) = pull_first(
                grouped_matmul(d_out, w_down_t, sizes)
            )
            d_rows = _cut_to(
                grouped_matmul(d_first, w_first_t, sizes), (chunk, hidden)
            )
        with jax.named_scope("combine"):
            d_tokens = d_tokens.at[at].add(
                _zeros_to(d_rows, (chunk, carry_w)).astype(jnp.float32),
                mode=_PIB,
            )
            d_weights = d_weights.at[slot].add(
                (expert_out.astype(jnp.float32) * g_rows).sum(axis=1),
                mode=_PIB, unique_indices=not padded,
            )
            saved = tuple(
                lax.dynamic_update_slice(buffer, part, (c * chunk, 0))
                for buffer, part in zip(
                    saved, (rows, d_first, act, d_out)
                )
            )
        return d_tokens, d_weights, saved

    # the loop itself is `combine`'s: its carries are the sums and the
    # buffers it fills, at the products' widths so that the stacks'
    # gradients below read them as they are
    with jax.named_scope("combine"):
        d_tokens, d_weights, (rows, d_first, act, d_out) = lax.fori_loop(
            0, _trips(group_sizes.sum(), chunk), trip,
            (
                jnp.zeros((tokens.shape[0], carry_w), jnp.float32),
                jnp.zeros(weights.shape, jnp.float32),
                tuple(
                    jnp.zeros((total * chunk, width), dtype) for width in
                    (wide.shape[1], w_first.shape[2], w_down.shape[1],
                     wide.shape[1])
                ),
            ),
        )
    with jax.named_scope("experts"):
        # rows past the last group are zero in all four buffers, so the
        # plain product needs none of `grouped_matmul`'s masks; what the
        # padding's zeros got is cut off
        (d_w_first,) = jax.vjp(
            lambda w: lax.ragged_dot(rows, w, group_sizes), w_first
        )[1](d_first)
        (d_w_down,) = jax.vjp(
            lambda w: lax.ragged_dot(act, w, group_sizes), w_down
        )[1](d_out)
        d_w_first = _first_as(d_w_first, parts, hidden, ffn, _cut_to)
        d_w_down = _cut_to(d_w_down, (w_down.shape[0], ffn, hidden))
    with jax.named_scope("combine"):
        d_tokens = _cut_to(d_tokens, tokens.shape).astype(dtype)
    return d_tokens, d_w_first, d_w_down, None, d_weights, None


routed_walk.defvjp(_walk_fwd, _walk_bwd)


class MoEMLP(nn.Module):
    """Top-1 (Switch) MoE feed-forward block: (..., hidden) -> (..., hidden).

    num_experts:     total experts (shard over the mesh `expert` axis)
    ffn_dim:         per-expert intermediate width
    capacity_factor: slots per expert = ceil(tokens/experts * factor)
    aux_loss_coef:   weight of the sown Switch load-balancing loss; the
                     Trainer adds everything sown into AUX_LOSS to the
                     training objective, so routing cannot collapse onto
                     one expert
    """

    num_experts: int
    ffn_dim: int
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        *batch_dims, hidden = x.shape
        tokens = x.reshape(-1, hidden)                      # (N, H)
        n_tokens = tokens.shape[0]
        capacity = max(
            1,
            int(-(-n_tokens * self.capacity_factor // self.num_experts)),
        )

        logits = nn.Dense(self.num_experts, name="router")(
            tokens.astype(jnp.float32)
        )                                                   # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)             # (N,)
        gate = jnp.take_along_axis(
            probs, expert_idx[:, None], axis=-1
        )[:, 0]                                             # (N,)

        # position of each token within its expert's queue (static shapes)
        onehot = jax.nn.one_hot(
            expert_idx, self.num_experts, dtype=jnp.int32
        )                                                   # (N, E)
        position = jnp.cumsum(onehot, axis=0) * onehot - 1  # (N, E)
        kept = (position >= 0) & (position < capacity)
        # dispatch: (N, E, C) one-hot; combine adds the gate weight
        pos_clipped = jnp.clip(position, 0, capacity - 1)
        dispatch = (
            jax.nn.one_hot(pos_clipped, capacity, dtype=tokens.dtype)
            * kept.astype(tokens.dtype)[..., None]
        )                                                   # (N, E, C)
        combine = dispatch * gate[:, None, None].astype(tokens.dtype)

        # route tokens to experts: XLA shards `e` (expert axis) and emits
        # the all-to-all from the shardings
        expert_in = jnp.einsum(
            "nec,nh->ech", dispatch, tokens.astype(self.compute_dtype)
        )                                                   # (E, C, H)

        w_in = self.param(
            "expert_w_in",
            nn.initializers.lecun_normal(),
            (self.num_experts, hidden, self.ffn_dim),
        )
        b_in = self.param(
            "expert_b_in", nn.initializers.zeros,
            (self.num_experts, self.ffn_dim),
        )
        w_out = self.param(
            "expert_w_out",
            nn.initializers.lecun_normal(),
            (self.num_experts, self.ffn_dim, hidden),
        )
        b_out = self.param(
            "expert_b_out", nn.initializers.zeros,
            (self.num_experts, hidden),
        )
        h = jnp.einsum(
            "ech,ehf->ecf", expert_in, w_in.astype(self.compute_dtype)
        ) + b_in[:, None, :].astype(self.compute_dtype)
        h = nn.relu(h)
        expert_out = jnp.einsum(
            "ecf,efh->ech", h, w_out.astype(self.compute_dtype)
        ) + b_out[:, None, :].astype(self.compute_dtype)    # (E, C, H)

        out = jnp.einsum(
            "nec,ech->nh", combine, expert_out.astype(jnp.float32)
        )
        # auxiliary load-balancing loss (Switch eq.4), pre-scaled by its
        # coefficient; the Trainer sums everything sown into AUX_LOSS
        # into the training objective (worker/trainer.py)
        density = expert_loads(expert_idx, self.num_experts) / n_tokens
        density_proxy = probs.mean(axis=0)
        self.sow(
            AUX_LOSS, "moe_aux_loss",
            self.aux_loss_coef
            * self.num_experts
            * jnp.sum(density * density_proxy),
        )
        return out.astype(x.dtype).reshape(*batch_dims, hidden)


class RoutedExperts(nn.Module):
    """Top-k routed experts, this holder's part: (..., hidden) ->
    (..., hidden) in float32.

        s = sigmoid(r Wr) | softmax(r Wr)     float32, all `num_experts`
        S = top_k(s + b)                      b selects, never weighs
        w_i = routed_scaling * s_i / (sum_{j in S} s_j + renorm_eps)
        out = sum_{i in S, i held here} w_i Expert_i(x)

    r is x, or `route_from` where the call gives one: a tensor of x's shape
    the router reads IN x'S PLACE (a block's input, where the model routes
    before its attention), while the experts still read x.  Everything the
    routing does then (the router's product, the scores, top-k, the sort
    and the group sizes) hangs on `route_from` alone and lies under the
    named scope `route_scope` besides `router` and `dispatch`: it waits on
    nothing the block computes between the two tensors.

    num_experts:      the router's width (every expert of the layer)
    held_experts:     (first, count) of the experts whose weights live
                      here; None holds all.  What absent experts would
                      add is left out (expert parallelism's partial sum;
                      the exchange is the caller's)
    bias_update_rate: gamma of loss-free balancing (arXiv:2408.15664):
                      b_i += gamma * sign(mean load - load_i) after each
                      train step, in ROUTER_STATE; 0 keeps b as it is
    renorm_eps:       added to the renormalisation's denominator (1e-6 in
                      `lfm2_moe`); 0.0 adds nothing to the program
    form:             an expert's activation (`FORMS`): `swiglu`, (silu(x
                      Wg) * (x Wu)) Wd, `reglu`, (relu(x Wg) * (x Wu)) Wd,
                      or `relu2`, (relu(x Wu))^2 Wd
    scores:           the router's scores (`SCORES`): `sigmoid`, each
                      expert's own, or `softmax` over all `num_experts`;
                      softmax scores are picked as they are: no selection
                      bias b and no ROUTER_STATE buffer
    route_scope:      the named scope of the routing where the call gives
                      a `route_from`; not entered without one

    Expert stacks are the form's first stack (`expert_w_gate_up`, gate
    and up fused, or `expert_w_up`) and `expert_w_down`, no biases;
    `moe_param_sharding` shards them.

    Cost: the router, top-k and sort over all tokens x top_k slots, then
    `routed_walk` over ceil(rows routed here / CHUNK) chunks; it sows
    `live_chunks_ratio` (chunks walked / chunks of the worst case; 1.0 =
    the walk saved nothing) beside `routed_here_ratio`; what the walk's
    padding adds to the products (`padded_work`) is a constant of the
    shapes, set in `worker_moe_padded_work_ratio` on the host.
    """

    num_experts: int
    top_k: int
    ffn_dim: int
    held_experts: Optional[Tuple[int, int]] = None
    routed_scaling: float = 1.0
    bias_update_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    renorm_eps: float = 0.0
    form: str = SWIGLU
    scores: str = SIGMOID
    route_scope: str = "route"

    @nn.compact
    def __call__(self, x, route_from=None):
        *lead, hidden = x.shape
        first_name, first_width, _ = FORMS[self.form]
        score_fn, biased = SCORES[self.scores], self.scores == SIGMOID
        with jax.named_scope("dispatch"):
            tokens = x.reshape(-1, hidden)
        n, k = tokens.shape[0], self.top_k
        first, count = self.held_experts or (0, self.num_experts)

        # the routing's two scopes lie under `route_scope` where the router
        # has a source of its own
        ahead = "" if route_from is None else self.route_scope + "/"

        with jax.named_scope(ahead + "router"):
            w_router = self.param(
                "router_kernel", nn.initializers.lecun_normal(),
                (hidden, self.num_experts), jnp.float32,
            )
            source = tokens if route_from is None else route_from.reshape(
                -1, hidden
            )
            scores = score_fn(jnp.dot(
                source.astype(jnp.float32), w_router,
                precision=jax.lax.Precision.HIGHEST,
            ))
            selection = jax.lax.stop_gradient(scores)
            if biased:
                bias = self.variable(
                    ROUTER_STATE, "e_score_correction_bias",
                    lambda: jnp.zeros((self.num_experts,), jnp.float32),
                )
                selection = selection + bias.value
            _, idx = jax.lax.top_k(selection, k)            # (n, k)
            idx = _named_flat(idx, PICKS_NAME)
            picked = _named_flat(
                jnp.take_along_axis(scores, idx, axis=1), PICKED_NAME
            )
            total = picked.sum(axis=1, keepdims=True)
            if self.renorm_eps:
                total = total + self.renorm_eps
            weights = self.routed_scaling * picked / total
            loads = expert_loads(idx, self.num_experts)
            if (self.bias_update_rate and biased
                    and not self.is_initializing()
                    and self.is_mutable_collection(ROUTER_STATE)):
                bias.value = bias.value + self.bias_update_rate * jnp.sign(
                    loads.mean() - loads
                )

        with jax.named_scope(ahead + "dispatch"):
            local = idx - first
            held = (local >= 0) & (local < count)
            # slots of absent experts sort past every held group
            key = jnp.where(held, local, count).reshape(-1)
            order = checkpoint_name(
                jnp.argsort(key, stable=True), ORDER_NAME
            )
            group_sizes = checkpoint_name(
                loads[first:first + count].astype(jnp.int32), SIZES_NAME
            )
            rows = group_sizes.sum()

        w_first = self.param(
            first_name, nn.initializers.lecun_normal(),
            (count, hidden, first_width * self.ffn_dim), jnp.float32,
        )
        w_down = self.param(
            "expert_w_down", nn.initializers.lecun_normal(),
            (count, self.ffn_dim, hidden), jnp.float32,
        )
        with jax.named_scope("dispatch"):
            tokens = tokens.astype(self.dtype)
        with jax.named_scope("experts"):
            # the stacks' casts are the experts' cost
            w_first = w_first.astype(self.dtype)
            w_down = w_down.astype(self.dtype)
        out = routed_walk(
            tokens, w_first, w_down, order, weights.reshape(-1),
            group_sizes, self.form,
        )
        chunk, total = _chunks(n * k)

        sow_step_metric(
            self, "expert_load_imbalance_ratio", loads.max() / loads.mean()
        )
        sow_step_metric(self, "routed_here_ratio", rows / (n * k))
        sow_step_metric(
            self, "live_chunks_ratio", _trips(rows, chunk) / total
        )
        sow_step_metric(self, "dropped_tokens", held.sum() - rows)
        # as a step that keeps the four above is traced: an `init` and the
        # trace that plans a block alone (`decoder.block_shapes`, which
        # keeps none) may not know the layer by its path in the model
        if (self.is_mutable_collection(STEP_METRICS)
                and not self.is_initializing()):
            padded_work_ratio.labels(layer="/".join(self.path)).set(
                padded_work(hidden, self.ffn_dim)
            )
        with jax.named_scope("combine"):
            return out.reshape(*lead, hidden)


def moe_param_sharding(path, value) -> Optional[P]:
    """`param_sharding` helper: stack-of-experts params shard their
    leading (expert) dim over the mesh `expert` axis; compose with other
    helpers for models that also have sharded embeddings."""
    names = [getattr(k, "key", str(k)) for k in path]
    if any(str(n).startswith("expert_") for n in names):
        ndim = getattr(value, "ndim", 0)
        if ndim >= 2:
            return P("expert", *([None] * (ndim - 1)))
        if ndim == 1:
            return P("expert")
    return None
