"""RecSys architectures: Wide&Deep, DIN, two-tower retrieval, DLRM-RM2.

The hot path is the sparse embedding lookup.  JAX has no native EmbeddingBag
— we build it from gather (+ ``segment_sum`` for multi-hot bags) as a
first-class substrate.  Tables are stacked (T, V, D) and row-sharded over the
"model" mesh axis; lookups against sharded tables become partial-gather +
cross-shard combine under GSPMD.

The two-tower model is the paper-integration point: its item tower fills the
corpus that the supermetric BSS index (repro.core.flat_index) serves exactly
(`retrieval_cand` cell = 1M-candidate scoring).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "RecsysConfig",
    "WideDeepModel",
    "DINModel",
    "TwoTowerModel",
    "DLRMModel",
]


def embedding_lookup(tables: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """tables (T, V, D), idx (B, T) one id per field -> (B, T, D)."""
    t = tables.shape[0]
    return tables[jnp.arange(t)[None, :], idx]


def embedding_bag(
    table: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray | None = None,
    combine: str = "mean",
) -> jnp.ndarray:
    """table (V, D), idx (B, L) multi-hot bag -> (B, D).  Manual EmbeddingBag:
    gather + masked reduce (the JAX-native formulation of nn.EmbeddingBag)."""
    e = table[idx]  # (B, L, D)
    if valid is not None:
        e = e * valid[..., None].astype(e.dtype)
        denom = jnp.maximum(valid.sum(axis=-1, keepdims=True), 1.0).astype(e.dtype)
    else:
        denom = jnp.asarray(idx.shape[1], e.dtype)
    s = e.sum(axis=1)
    return s / denom if combine == "mean" else s


def _mlp_shapes(dims: Sequence[int], dtype) -> list:
    return [((dims[i], dims[i + 1]), dtype) for i in range(len(dims) - 1)]


def _mlp_apply(x, ws, bs, final_act=False):
    for i, (w, b) in enumerate(zip(ws, bs)):
        x = x @ w + b
        if i < len(ws) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def _constrain_rows(x, batch_axes):
    """Pin the batch/row sharding of an embedding-lookup output; GSPMD's
    gather partitioning otherwise replicates it (see transformer.py note)."""
    if batch_axes is None:
        return x
    from repro.parallel.sharding import maybe_constrain

    return maybe_constrain(
        x, P(tuple(batch_axes), *([None] * (x.ndim - 1)))
    )


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str                      # wide_deep | din | two_tower | dlrm
    n_sparse: int = 26
    n_dense: int = 0
    embed_dim: int = 64
    vocab: int = 1_000_000         # rows per table (assignment leaves this
                                   # open; kernel-taxonomy D.6 regime 10^6)
    mlp: tuple = (1024, 512, 256)
    bot_mlp: tuple = ()
    attn_mlp: tuple = (80, 40)
    hist_len: int = 100
    tower_mlp: tuple = (1024, 512, 256)
    n_user_fields: int = 8
    n_item_fields: int = 4
    dtype: Any = jnp.bfloat16
    optimizer: str = "adamw"
    microbatches: int = 1
    batch_axes: tuple | None = None


class _RecsysBase:
    def __init__(self, cfg: RecsysConfig):
        self.cfg = cfg

    def abstract_params(self) -> dict:
        def to_sds(x):
            return jax.ShapeDtypeStruct(x[0], x[1])

        return jax.tree.map(
            to_sds, self.param_shapes(), is_leaf=lambda v: isinstance(v, tuple)
            and len(v) == 2 and isinstance(v[0], tuple)
        )

    def init_params(self, rng) -> dict:
        flat = jax.tree.leaves(
            self.param_shapes(),
            is_leaf=lambda v: isinstance(v, tuple) and len(v) == 2
            and isinstance(v[0], tuple),
        )
        treedef = jax.tree.structure(
            self.param_shapes(),
            is_leaf=lambda v: isinstance(v, tuple) and len(v) == 2
            and isinstance(v[0], tuple),
        )
        keys = jax.random.split(rng, len(flat))
        leaves = []
        for k, (shape, dt) in zip(keys, flat):
            fan = shape[-2] if len(shape) > 1 else shape[-1]
            leaves.append(
                (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)).astype(dt)
            )
        return jax.tree.unflatten(treedef, leaves)

    def param_specs(self, mesh: Mesh) -> dict:
        def spec(v):
            shape, _ = v
            if len(shape) == 3:  # stacked tables (T, V, D): rows over model
                return P(None, "model", None)
            if len(shape) == 2 and shape[0] >= 65536:  # big single table
                return P("model", None)
            return P(*([None] * len(shape)))

        return jax.tree.map(
            spec, self.param_shapes(), is_leaf=lambda v: isinstance(v, tuple)
            and len(v) == 2 and isinstance(v[0], tuple)
        )


class WideDeepModel(_RecsysBase):
    """Wide&Deep (arXiv:1606.07792): wide hashed-linear + deep MLP on field
    embeddings, summed logits."""

    def param_shapes(self) -> dict:
        c = self.cfg
        deep_in = c.n_sparse * c.embed_dim
        dims = (deep_in,) + tuple(c.mlp) + (1,)
        return {
            "tables": ((c.n_sparse, c.vocab, c.embed_dim), c.dtype),
            "wide": ((c.vocab, 1), c.dtype),
            "mlp_w": _mlp_shapes(dims, c.dtype),
            "mlp_b": [((d,), c.dtype) for d in dims[1:]],
        }

    def forward(self, params: dict, batch: dict) -> jnp.ndarray:
        c = self.cfg
        idx = batch["sparse_ids"]  # (B, T)
        emb = _constrain_rows(embedding_lookup(params["tables"], idx), c.batch_axes)
        deep = _mlp_apply(
            emb.reshape(emb.shape[0], -1), params["mlp_w"], params["mlp_b"]
        )
        wide = params["wide"][idx % c.vocab][..., 0].sum(axis=-1, keepdims=True)
        return (deep + wide).astype(jnp.float32)[:, 0]


class DINModel(_RecsysBase):
    """Deep Interest Network (arXiv:1706.06978): target attention over the
    user behaviour sequence."""

    def param_shapes(self) -> dict:
        c = self.cfg
        d = c.embed_dim
        attn_dims = (4 * d,) + tuple(c.attn_mlp) + (1,)
        mlp_dims = (2 * d,) + tuple(c.mlp) + (1,)
        return {
            "item_table": ((c.vocab, d), c.dtype),
            "attn_w": _mlp_shapes(attn_dims, c.dtype),
            "attn_b": [((x,), c.dtype) for x in attn_dims[1:]],
            "mlp_w": _mlp_shapes(mlp_dims, c.dtype),
            "mlp_b": [((x,), c.dtype) for x in mlp_dims[1:]],
        }

    def forward(self, params: dict, batch: dict) -> jnp.ndarray:
        hist = batch["hist_ids"]        # (B, L)
        target = batch["target_id"]     # (B,)
        valid = batch.get("hist_valid")  # (B, L) bool
        eh = _constrain_rows(params["item_table"][hist], self.cfg.batch_axes)
        et = _constrain_rows(
            params["item_table"][target], self.cfg.batch_axes
        )[:, None, :]
        etb = jnp.broadcast_to(et, eh.shape)
        a_in = jnp.concatenate([eh, etb, eh - etb, eh * etb], axis=-1)
        w = _mlp_apply(a_in, params["attn_w"], params["attn_b"])[..., 0]  # (B, L)
        if valid is not None:
            w = jnp.where(valid, w, -1e9)
        w = jax.nn.softmax(w.astype(jnp.float32), axis=-1).astype(eh.dtype)
        user = jnp.einsum("bl,bld->bd", w, eh)
        z = jnp.concatenate([user, et[:, 0]], axis=-1)
        return _mlp_apply(z, params["mlp_w"], params["mlp_b"]).astype(jnp.float32)[:, 0]


class TwoTowerModel(_RecsysBase):
    """Two-tower retrieval (Yi et al., RecSys'19): user/item towers -> dot;
    trained with in-batch sampled softmax (logQ-free synthetic variant)."""

    def param_shapes(self) -> dict:
        c = self.cfg
        d_emb = 64  # per-field embedding feeding the towers
        u_in = c.n_user_fields * d_emb
        i_in = c.n_item_fields * d_emb
        u_dims = (u_in,) + tuple(c.tower_mlp) + (c.embed_dim,)
        i_dims = (i_in,) + tuple(c.tower_mlp) + (c.embed_dim,)
        return {
            "user_tables": ((c.n_user_fields, c.vocab, d_emb), c.dtype),
            "item_tables": ((c.n_item_fields, c.vocab, d_emb), c.dtype),
            "user_w": _mlp_shapes(u_dims, c.dtype),
            "user_b": [((x,), c.dtype) for x in u_dims[1:]],
            "item_w": _mlp_shapes(i_dims, c.dtype),
            "item_b": [((x,), c.dtype) for x in i_dims[1:]],
        }

    def user_embed(self, params, user_ids):
        e = _constrain_rows(
            embedding_lookup(params["user_tables"], user_ids), self.cfg.batch_axes
        )
        z = _mlp_apply(e.reshape(e.shape[0], -1), params["user_w"], params["user_b"])
        return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-6)

    def item_embed(self, params, item_ids):
        e = _constrain_rows(
            embedding_lookup(params["item_tables"], item_ids), self.cfg.batch_axes
        )
        z = _mlp_apply(e.reshape(e.shape[0], -1), params["item_w"], params["item_b"])
        return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-6)

    def forward(self, params: dict, batch: dict) -> jnp.ndarray:
        if "candidates" in batch:  # retrieval scoring: precomputed item matrix
            u = self.user_embed(params, batch["user_ids"])  # (B, E)
            return (u.astype(jnp.float32) @ batch["candidates"].astype(jnp.float32).T)
        u = self.user_embed(params, batch["user_ids"])
        i = self.item_embed(params, batch["item_ids"])
        return (u.astype(jnp.float32) @ i.astype(jnp.float32).T) * 20.0  # temp

    def loss_fn(self, params: dict, batch: dict) -> jnp.ndarray:
        logits = self.forward(params, batch)  # (B, B) in-batch softmax
        labels = jnp.arange(logits.shape[0])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    def forward_retrieval_pruned(
        self, params: dict, batch: dict, *, block: int = 128,
        budget_blocks: int = 3136,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Supermetric-pruned candidate scoring (the paper's technique in the
        serving graph).  batch adds the BSS index arrays:
            pivots (P, E) fp32, pair_idx (M, 2) i32, deltas (M,) fp32,
            boxes (4, M, B_blocks) fp32 (the index's planes-major table).
        Only the ``budget_blocks`` blocks with the smallest planar lower
        bound are gathered and scored — candidate HBM reads drop by
        B/budget (2.5x at the default).  Exact for any top-k whose k-th
        distance exceeds the (budget+1)-th block bound (serving layer
        verifies and widens, see serve/retrieval.py).

        Returns (scores (Q, budget*block), candidate row indices)."""
        u = self.user_embed(params, batch["user_ids"])  # (Q, E) normalised
        cand = batch["candidates"]
        piv = batch["pivots"]
        pairs = batch["pair_idx"]
        deltas = batch["deltas"]
        boxes = jnp.transpose(batch["boxes"], (2, 1, 0))  # (B_blocks, M, 4)
        n, e_dim = cand.shape
        b_blocks = boxes.shape[0]
        n_pad = b_blocks * block

        uf = u.astype(jnp.float32)
        dq = jnp.sqrt(jnp.maximum(
            jnp.sum(uf * uf, -1)[:, None]
            + jnp.sum(piv * piv, -1)[None, :]
            - 2.0 * uf @ piv.T, 0.0,
        ))  # (Q, P)
        d1 = dq[:, pairs[:, 0]]
        d2 = dq[:, pairs[:, 1]]
        delta = jnp.maximum(deltas[None, :], 1e-12)
        qx = (d1 * d1 - d2 * d2) / (2.0 * delta)
        qy = jnp.sqrt(jnp.maximum(d1 * d1 - (qx + delta / 2.0) ** 2, 0.0))
        dx = jnp.maximum(jnp.maximum(boxes[None, :, :, 0] - qx[:, None, :],
                                     qx[:, None, :] - boxes[None, :, :, 1]), 0.0)
        dy = jnp.maximum(jnp.maximum(boxes[None, :, :, 2] - qy[:, None, :],
                                     qy[:, None, :] - boxes[None, :, :, 3]), 0.0)
        lb = jnp.max(jnp.sqrt(dx * dx + dy * dy), axis=-1)  # (Q, B_blocks)

        # Rank blocks by (lower bound, distance-to-box-centres): overlapping
        # boxes give lb == 0 ties for most blocks, where the bound alone
        # degenerates to block-id order (an arbitrary subset).  The centre
        # proximity is a pure ordering heuristic — soundness/exactness only
        # ever depend on WHICH blocks are inside the budget being verified
        # downstream, never on this tie-break.
        cx = 0.5 * (boxes[None, :, :, 0] + boxes[None, :, :, 1])
        cy = 0.5 * (boxes[None, :, :, 2] + boxes[None, :, :, 3])
        cdist = jnp.mean(
            jnp.sqrt((qx[:, None, :] - cx) ** 2 + (qy[:, None, :] - cy) ** 2),
            axis=-1,
        )  # (Q, B_blocks)
        order = jnp.lexsort((cdist, lb), axis=1)  # lb primary, cdist ties
        top = order[:, :budget_blocks]  # (Q, budget)
        cand_pad = jnp.pad(cand, ((0, n_pad - n), (0, 0)))
        blocks = cand_pad.reshape(b_blocks, block, e_dim)
        picked = blocks[top]  # (Q, budget, block, E) — the pruned gather
        scores = jnp.einsum(
            "qe,qkbe->qkb", u.astype(jnp.float32), picked.astype(jnp.float32)
        ).reshape(u.shape[0], -1)
        rows = (top[..., None] * block
                + jnp.arange(block)[None, None, :]).reshape(u.shape[0], -1)
        return scores, rows


class DLRMModel(_RecsysBase):
    """DLRM-RM2 (arXiv:1906.00091): bottom MLP on dense feats, dot-product
    feature interaction, top MLP."""

    def param_shapes(self) -> dict:
        c = self.cfg
        d = c.embed_dim
        bot = (c.n_dense,) + tuple(c.bot_mlp)
        n_f = c.n_sparse + 1
        n_inter = n_f * (n_f - 1) // 2
        top = (n_inter + d,) + tuple(c.mlp) + (1,)
        return {
            "tables": ((c.n_sparse, c.vocab, d), c.dtype),
            "bot_w": _mlp_shapes(bot, c.dtype),
            "bot_b": [((x,), c.dtype) for x in bot[1:]],
            "top_w": _mlp_shapes(top, c.dtype),
            "top_b": [((x,), c.dtype) for x in top[1:]],
        }

    def forward(self, params: dict, batch: dict) -> jnp.ndarray:
        dense = batch["dense"].astype(self.cfg.dtype)  # (B, 13)
        idx = batch["sparse_ids"]  # (B, 26)
        z0 = _mlp_apply(dense, params["bot_w"], params["bot_b"], final_act=True)
        emb = _constrain_rows(
            embedding_lookup(params["tables"], idx), self.cfg.batch_axes
        )  # (B, 26, D)
        z = jnp.concatenate([z0[:, None, :], emb], axis=1)  # (B, 27, D)
        inter = jnp.einsum("bnd,bmd->bnm", z, z)  # (B, 27, 27)
        iu, ju = jnp.triu_indices(z.shape[1], k=1)
        feat = jnp.concatenate([inter[:, iu, ju], z0], axis=-1)
        return _mlp_apply(feat, params["top_w"], params["top_b"]).astype(jnp.float32)[:, 0]


def bce_loss(model, params: dict, batch: dict) -> jnp.ndarray:
    logit = model.forward(params, batch)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    )
