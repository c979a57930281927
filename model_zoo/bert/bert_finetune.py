"""BERT sequence-classification fine-tune (BASELINE.md config #5 — the
elasticity headline config, and the long-context flagship).

Zoo-contract port of the reference's BERT fine-tune example (SURVEY.md
C20), re-designed TPU-first:

- attention is RING attention over the mesh `seq` axis
  (elasticdl_tpu.ops.ring_attention): K/V blocks rotate over ICI with
  online-softmax accumulation, so sequence length scales with the number
  of chips — capability the reference does not have (SURVEY.md §5:
  upstream has no SP/CP);
- the token-embedding table is a DistributedEmbedding row-sharded over the
  `model` axis;
- everything else (QKV projections, MLP) is MXU matmuls that XLA shards
  from the batch/sequence NamedShardings.

Record format: max_len int32 token ids | 1 uint8 label.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers.embedding import (
    DistributedEmbedding,
    embedding_param_sharding,
)
from elasticdl_tpu.ops.ring_attention import ring_self_attention
from elasticdl_tpu.parallel.mesh import get_current_mesh
from model_zoo.common.metrics import auc, binary_accuracy

MAX_LEN = 128
VOCAB_SIZE = 8192


class RingSelfAttention(nn.Module):
    hidden: int
    heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        head_dim = self.hidden // self.heads
        qkv = nn.Dense(3 * self.hidden, name="qkv", dtype=self.dtype)(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (batch, length, self.heads, head_dim)
        out = ring_self_attention(
            q.reshape(shape), k.reshape(shape), v.reshape(shape),
            mesh=get_current_mesh(), causal=False,
        )
        return nn.Dense(self.hidden, name="out", dtype=self.dtype)(
            out.reshape(batch, length, self.hidden)
        )


class LocalSelfAttention(nn.Module):
    """Mesh-free attention for pipelined blocks: runs INSIDE the pipeline's
    shard_map, so it must not open its own (ring attention does).  Uses the
    on-chip Pallas flash kernel when the shape tiles, else the fused-lax
    reference path."""

    hidden: int
    heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from elasticdl_tpu.ops.flash_attention import (
            use_interpret,
            flash_attention,
            flash_shapes_ok,
        )
        from elasticdl_tpu.ops.ring_attention import full_attention_reference

        batch, length, _ = x.shape
        head_dim = self.hidden // self.heads
        qkv = nn.Dense(3 * self.hidden, name="qkv", dtype=self.dtype)(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (batch, length, self.heads, head_dim)
        q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
        # Explicit tile-shape dispatch — a try/except here once swallowed
        # an unrelated shard_map typing error and silently took the
        # O(L^2) path (round-5 profile finding).  Compiled kernel only:
        # this runs INSIDE the pipeline's vma-audited shard_map, where
        # the interpreter's block-slicing internals fail the audit; the
        # reference path is the same math, and the kernel itself is
        # covered by tests/test_flash_attention.py in interpret mode.
        if not use_interpret() and flash_shapes_ok(q.shape, k.shape):
            out = flash_attention(q, k, v, causal=False)
        else:
            out = full_attention_reference(q, k, v, causal=False)
        return nn.Dense(self.hidden, name="out", dtype=self.dtype)(
            out.reshape(batch, length, self.hidden)
        )


class PipelinedBlock(nn.Module):
    """Shape-preserving transformer block for the GPipe stack (attention
    tier is local-only; sequence and expert axes belong to the non-
    pipelined path)."""

    hidden: int
    heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = LocalSelfAttention(
            self.hidden, self.heads, dtype=self.dtype, name="attention"
        )(x)
        x = nn.LayerNorm(dtype=self.dtype)(x + y)
        y = nn.Dense(self.mlp_dim, dtype=self.dtype)(x)
        y = nn.gelu(y)
        y = nn.Dense(self.hidden, dtype=self.dtype)(y)
        return nn.LayerNorm(dtype=self.dtype)(x + y)


class TransformerBlock(nn.Module):
    hidden: int
    heads: int
    mlp_dim: int
    # > 0 replaces the dense FFN with a Switch MoE block of this many
    # experts, sharded over the mesh `expert` axis (expert parallelism —
    # capability the reference does not have)
    moe_experts: int = 0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = RingSelfAttention(
            self.hidden, self.heads, dtype=self.dtype, name="attention"
        )(x)
        x = nn.LayerNorm(dtype=self.dtype)(x + y)
        if self.moe_experts > 0:
            from elasticdl_tpu.layers.moe import MoEMLP

            y = MoEMLP(
                num_experts=self.moe_experts, ffn_dim=self.mlp_dim,
                name="moe_mlp",
            )(x)
        else:
            y = nn.Dense(self.mlp_dim, dtype=self.dtype)(x)
            y = nn.gelu(y)
            y = nn.Dense(self.hidden, dtype=self.dtype)(y)
        return nn.LayerNorm(dtype=self.dtype)(x + y)


class BertClassifier(nn.Module):
    vocab_size: int = VOCAB_SIZE
    hidden: int = 768
    num_layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = MAX_LEN
    num_classes: int = 2
    moe_experts: int = 0
    # > 0 stacks the encoder blocks into a GPipe pipeline over the mesh
    # `pipe` axis with this many microbatches (pipeline parallelism —
    # capability the reference does not have).  Mutually exclusive with
    # moe_experts (the pipelined block is local-attention + dense FFN).
    pipeline_microbatches: int = 0
    # Rematerialize each encoder block in the backward pass
    # (jax.checkpoint via nn.remat): peak activation memory drops from
    # all-layers-live to one-layer-live, trading ~1/3 more FLOPs — the
    # standard TPU answer when long sequences blow HBM (measured:
    # BERT-base at L=2048, batch 16 needs 18.7 GB without remat on a
    # 16 GB v5e, and trains with it).  Param tree unchanged, so
    # checkpoints move freely between remat and non-remat configs.
    remat: bool = False
    # bf16 matmuls run the MXU at full rate (4x the f32 rate on v5e);
    # params stay f32 (flax param_dtype default).  LayerNorms compute in
    # the same dtype (halves their HBM traffic — the step is partly
    # bound by normalization/residual bandwidth); the embedding-input LN
    # and the classifier head stay f32.
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, features):
        ids = features["input_ids"].astype(jnp.int32)      # (B, L)
        tok = DistributedEmbedding(
            self.vocab_size, self.hidden, hash_input=False,
            name="token_embedding",
        )(ids)
        pos = self.param(
            "position_embedding",
            nn.initializers.normal(0.02),
            (self.max_len, self.hidden),
        )
        x = tok + pos[None, : ids.shape[1]]
        x = nn.LayerNorm()(x)
        if self.pipeline_microbatches > 0:
            if self.moe_experts > 0:
                raise ValueError(
                    "pipeline_microbatches and moe_experts are mutually "
                    "exclusive"
                )
            from elasticdl_tpu.layers.pipeline import GPipeBlocks

            x = GPipeBlocks(
                block_cls=PipelinedBlock,
                block_kwargs={
                    "hidden": self.hidden, "heads": self.heads,
                    "mlp_dim": self.mlp_dim, "dtype": self.dtype,
                },
                num_layers=self.num_layers,
                num_microbatches=self.pipeline_microbatches,
                remat=self.remat,
                name="encoder_pipeline",
            )(x)
        else:
            block_cls = (
                nn.remat(TransformerBlock) if self.remat
                else TransformerBlock
            )
            for i in range(self.num_layers):
                x = block_cls(
                    self.hidden, self.heads, self.mlp_dim,
                    moe_experts=self.moe_experts, dtype=self.dtype,
                    name=f"layer_{i}",
                )(x)
        # max-pool over sequence: sharp feature detection, and ring-
        # friendly (a cross-shard reduce, no CLS gather from one shard)
        pooled = jnp.max(x, axis=1)
        logits = nn.Dense(self.num_classes, name="classifier")(pooled)
        return logits


def custom_model(hidden: int = 768, num_layers: int = 12, heads: int = 12,
                 mlp_dim: int = 3072, max_len: int = MAX_LEN,
                 vocab_size: int = VOCAB_SIZE, moe_experts: int = 0,
                 pipeline_microbatches: int = 0, bf16: bool = False,
                 remat: bool = False):
    return BertClassifier(
        vocab_size=vocab_size, hidden=hidden, num_layers=num_layers,
        heads=heads, mlp_dim=mlp_dim, max_len=max_len,
        dtype=jnp.bfloat16 if bf16 else jnp.float32,
        moe_experts=moe_experts,
        pipeline_microbatches=pipeline_microbatches,
        remat=remat,
    )


def loss(labels, predictions):
    return optax.softmax_cross_entropy_with_integer_labels(
        predictions, labels.astype(jnp.int32)
    ).mean()


def optimizer(lr: float = 2e-5):
    return optax.adamw(lr, weight_decay=0.01)


def feed(records, metadata=None, max_len: int = MAX_LEN):
    ids = np.empty((len(records), max_len), np.int32)
    labels = np.empty((len(records),), np.int32)
    for i, record in enumerate(records):
        if isinstance(record, dict):
            ids[i] = record["input_ids"]
            labels[i] = record["label"]
        else:
            ids[i] = np.frombuffer(record, np.int32, max_len, 0)
            labels[i] = record[max_len * 4]
    return {"features": {"input_ids": ids}, "labels": labels}


def feed_bulk(buffer, sizes, metadata=None):
    """Vectorized parse of the fixed-width record (max_len int32 ids + 1
    label byte); max_len is derived from the record size, so one parser
    serves every dataset length."""
    sizes = np.asarray(sizes)
    n = len(sizes)
    if n == 0 or not (sizes == sizes[0]).all() or sizes[0] % 4 != 1:
        raise ValueError(
            "bert feed_bulk expects fixed-width 4*max_len+1 byte records"
        )
    rec = int(sizes[0])
    arr = np.frombuffer(buffer, np.uint8).reshape(n, rec)
    ids = np.ascontiguousarray(arr[:, : rec - 1]).view("<i4")
    return {
        "features": {"input_ids": ids},
        "labels": arr[:, rec - 1].astype(np.int32),
    }


def feed_bulk_compact(buffer, sizes, metadata=None):
    """feed_bulk with the compact device wire format
    (elasticdl_tpu.data.wire): token ids as uint16 (this zoo's default
    vocab is 8192; any vocab <= 65536 fits), labels uint8 — halves the
    record's host->device bytes.  The model casts ids to int32 at entry,
    so no model change is needed."""
    batch = feed_bulk(buffer, sizes, metadata)
    ids = batch["features"]["input_ids"]
    if ids.size and (ids.min() < 0 or ids.max() >= 1 << 16):
        raise ValueError(
            "bert feed_bulk_compact needs token ids in [0, 65536); this "
            "dataset's don't fit uint16 — use the standard feed"
        )
    return {
        "features": {"input_ids": ids.astype(np.uint16)},
        "labels": batch["labels"].astype(np.uint8),
    }


def eval_metrics_fn():
    return {
        "accuracy": lambda labels, predictions: float(
            np.mean(np.argmax(predictions, -1) == labels)
        ),
        "auc": lambda labels, predictions: auc(
            labels, predictions[:, 1] - predictions[:, 0]
        ),
    }


def param_sharding(path, value):
    """Sharded embedding tables over `model`, expert stacks over `expert`
    (when moe_experts > 0), pipelined layer stacks over `pipe` (when
    pipeline_microbatches > 0); everything else replicated."""
    from elasticdl_tpu.layers.moe import moe_param_sharding
    from elasticdl_tpu.layers.pipeline import pipeline_param_sharding

    spec = pipeline_param_sharding(path, value)
    if spec is not None:
        return spec
    spec = moe_param_sharding(path, value)
    if spec is not None:
        return spec
    return embedding_param_sharding(path, value)
