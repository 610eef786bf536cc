"""Sharding rules: param/activation/cache PartitionSpecs over the
production mesh (DESIGN.md §5).

Strategy (baseline): FSDP over the "data" axis × Megatron-style TP/EP over
the "model" axis; "pod" is an outer pure-DP axis (batch + gradient
reduction only — ICI-heavy collectives never cross it). Every rule is a
*preference*: the resolver drops any axis whose size does not divide the
corresponding dim (e.g. 8 KV heads on a 16-way model axis), so one rule
table covers all ten architectures.

The rule table is keyed by (context, leaf-name) where context is the
nearest enclosing component ("mixer" / "ffn" / "shared" / top-level) —
that disambiguates e.g. GQA's 3-D ``w_k`` from RWKV channel-mix's 2-D
``w_k``. Logical axes are then mapped onto mesh axes through
:class:`ShardingRules`, the hillclimbing surface for §Perf.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Params = dict[str, Any]

# ---------------------- activation-constraint context ------------------------
# Model code calls ``constrain(x, logical_axes)`` at a few key points
# (tied-head weight, MoE dispatch, sequence sharding). Outside an
# ``activate(mesh, rules)`` scope it is a no-op, so plain CPU tests and
# examples never touch mesh machinery.

_ACTIVE: list[tuple[Mesh, "ShardingRules"]] = []


@contextlib.contextmanager
def activate(mesh: Mesh, rules: "ShardingRules | None" = None):
    _ACTIVE.append((mesh, rules or ShardingRules()))
    try:
        yield
    finally:
        _ACTIVE.pop()


def constrain(x, logical: tuple):
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    spec = _resolve_spec(tuple(logical), x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def active_rules() -> "ShardingRules | None":
    return _ACTIVE[-1][1] if _ACTIVE else None


def embed_lookup(table, ids):
    """Distributed embedding lookup: masked local take + psum.

    GSPMD's gather partitioning hits an XLA verifier bug for several of
    the assigned archs (dynamic-slice of the sharded table's full dim —
    see EXPERIMENTS.md §Dry-run), and its backward materializes a
    full-size dW scatter buffer. This shard_map formulation is the
    standard Megatron vocab-parallel embedding: each vocab shard looks up
    the ids it owns, zeros the rest, and one small psum over the vocab
    axis assembles the row. Backward is a local scatter-add (dW stays
    sharded). Outside activate() (CPU tests), falls back to table[ids].
    """
    if not _ACTIVE:
        return table[ids]
    import jax.numpy as jnp

    mesh, rules = _ACTIVE[-1]
    tbl_spec = tuple(_resolve_spec(_TOP["embed"], table.shape, mesh, rules))
    tbl_spec += (None,) * (2 - len(tbl_spec))
    ids_spec = tuple(_resolve_spec(("batch", "seq"), ids.shape, mesh, rules))
    ids_spec += (None,) * (ids.ndim - len(ids_spec))
    vocab_axes, d_axes = tbl_spec
    # ids must be REPLICATED over the vocab axes (the psum below sums
    # vocab shards of the SAME id set — a batch axis shared with the
    # vocab axis would sum different batch shards' rows), and must not
    # collide with the output's d sharding either.
    v_ax = set(
        vocab_axes if isinstance(vocab_axes, tuple) else (vocab_axes,)
    ) - {None}
    forbidden = v_ax | ({d_axes} - {None})
    def _strip(s):
        if s is None:
            return None
        parts = tuple(a for a in (s if isinstance(s, tuple) else (s,))
                      if a not in forbidden)
        return parts if len(parts) > 1 else (parts[0] if parts else None)
    ids_spec = tuple(_strip(s) for s in ids_spec)
    if vocab_axes is None:
        # table not vocab-sharded → plain gather partitions fine
        out = table[ids]
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(*(ids_spec + (d_axes,))))
        )

    axes = vocab_axes if isinstance(vocab_axes, tuple) else (vocab_axes,)

    def local(tbl, ids_local):
        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        vloc = tbl.shape[0]
        lo = idx * vloc
        loc = ids_local - lo
        ok = (loc >= 0) & (loc < vloc)
        rows = jnp.take(tbl, jnp.clip(loc, 0, vloc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, axes)

    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(*( (vocab_axes, tbl_spec[1]) )), P(*ids_spec)),
        out_specs=P(*(ids_spec + (tbl_spec[1],))),
        check_vma=False,
    )(table, ids)
    # re-shard the rows onto the batch axes for the downstream layers
    final = tuple(_resolve_spec(("batch", "seq"), ids.shape, mesh, rules))
    final += (None,) * (ids.ndim - len(final))
    final = tuple(_strip(s) if s and (set(
        s if isinstance(s, tuple) else (s,)) & ({d_axes} - {None})) else s
        for s in final)
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(*(final + (d_axes,))))
    )


def constrain_like_params(tree):
    """Pin a param-shaped pytree (e.g. gradients) to the parameter
    sharding rules. Without this, GSPMD materializes full-size f32
    gradient accumulators for scatter-producing backward ops (embedding
    tables: ~2 GiB each at 102k×5120) before sharding them; with it, the
    dW reduce-scatter happens at production. No-op outside activate()."""
    if not _ACTIVE:
        return tree
    mesh, rules = _ACTIVE[-1]
    specs = param_pspecs(None, tree, mesh, rules)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))
        if jax.numpy.issubdtype(x.dtype, jax.numpy.inexact)
        else x,
        tree,
        specs,
    )

# ------------------------------ rules ----------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical→mesh axis assignment. Fields are hillclimb levers."""

    batch_axes: tuple[str, ...] = ("pod", "data")  # batch dim of activations
    fsdp_axis: str | None = "data"  # weight "replicated-ish" dims
    tp_axis: str | None = "model"  # heads / mlp / experts / vocab
    shard_vocab: bool = True  # embed+lm_head over tp_axis
    cache_seq_axis: str | None = "model"  # decode KV/latent cache seq dim
    seq_axis: str | None = None  # sequence parallelism (prefill/train)

    def resolve(self, logical: str | None):
        if logical is None:
            return None
        if logical == "batch":
            return self.batch_axes
        if logical == "fsdp":
            return self.fsdp_axis
        if logical == "tp":
            return self.tp_axis
        if logical == "vocab":
            return self.tp_axis if self.shard_vocab else None
        if logical == "cache_seq":
            return self.cache_seq_axis
        if logical == "seq":
            return self.seq_axis
        if logical == "row_blocks":
            # Sparse-weight shard dim (ShardedBlockCSR leading axis / BSR
            # row-block dim): a dedicated "row_blocks" mesh axis when the
            # mesh has one (launch.mesh.make_row_blocks_mesh), else fully
            # sharded over every compute axis. The resolver drops names
            # absent from the mesh, so one rule covers both mesh styles.
            axes = ("row_blocks",) + tuple(
                a for a in (self.fsdp_axis, self.tp_axis) if a
            )
            return axes or None
        raise ValueError(f"unknown logical axis {logical!r}")


# (context, name) -> tuple of logical axes per dim. "fsdp" ~ d_model-like
# dims (sharded for FSDP storage), "tp" ~ heads/mlp/expert dims.
_MIXER = {
    # GQA
    "w_q": ("fsdp", "tp", None),
    "w_k": ("fsdp", "tp", None),
    "w_v": ("fsdp", "tp", None),
    "w_o": ("tp", None, "fsdp"),
    "b_q": ("tp", None),
    "b_k": ("tp", None),
    "b_v": ("tp", None),
    # MLA
    "w_dq": ("fsdp", None),
    "w_uq": (None, "tp", None),
    "w_dkv": ("fsdp", None),
    "w_uk": (None, "tp", None),
    "w_uv": (None, "tp", None),
    # Mamba (di = expand·d_model is the "tp" dim)
    "in_proj": ("fsdp", "tp"),
    "conv_w": (None, "tp"),
    "conv_b": ("tp",),
    "x_proj": ("tp", None),
    "dt_proj": (None, "tp"),
    "dt_bias": ("tp",),
    "A_log": ("tp", None),
    "D": ("tp",),
    "out_proj": ("tp", "fsdp"),
    # RWKV-6 time mix (square d→d projections: column-parallel in, row-
    # parallel out; small LoRA/mix tensors stay replicated)
    "w_r": ("fsdp", "tp"),
    "w_g": ("fsdp", "tp"),
    "mix_w1": ("fsdp", None),
    "mix_w2": (None, None, "fsdp"),
    "decay_w1": ("fsdp", None),
    "decay_w2": (None, "fsdp"),
    "mu_x": (None,),
    "mu": (None, None),
    "w0": (None,),
    "bonus_u": (None, None),
}
_FFN = {
    # dense FFN / GLU
    "w_in": ("fsdp", "tp"),
    "w_gate": ("fsdp", "tp"),
    "w_out": ("tp", "fsdp"),
    # MoE expert banks (leading experts dim = EP over tp_axis)
    "router": ("fsdp", None),
    # RWKV channel mix
    "w_k": ("fsdp", "tp"),
    "w_v": ("tp", "fsdp"),
    "w_r": ("fsdp", None),
    "mu_k": (None,),
    "mu_r": (None,),
    # the paper's MLP layer (square m×m weight, x @ W input-major)
    "w": ("fsdp", "tp"),
    "b": ("tp",),
}
_MOE_BANK = {  # 3-D expert banks, disambiguated by ndim
    # EP over the model axis (e) × Megatron column/row split of the
    # expert FF dim over the data axis. FSDP-style d_model sharding of
    # expert banks is deliberately avoided: it turns every expert matmul
    # into a partial-sum all-reduce of (tokens×d_ff) activations, which
    # dwarfs the f-shard weight halves (measured: §Perf deepseek cell).
    "w_in": ("tp", None, "fsdp"),
    "w_gate": ("tp", None, "fsdp"),
    "w_out": ("tp", "fsdp", None),
}
_TOP = {
    # embed is 2-D sharded for storage (vocab over data, d over tp); the
    # lookup gathers from the d-shard (vocab side resolved by GSPMD via
    # masked local lookup + reduce). The tied-head matmul reshards it on
    # the fly — see Model._head + constrain().
    "embed": ("fsdp", "tp"),
    "lm_head": ("fsdp", "vocab"),
}
# BSR weight leaves (output-major: row blocks = output dim → tp)
_BSR = {
    "blocks": ("tp", None, None, None),
    "col_idx": ("tp", None),
    "block_mask": ("tp", None),
}
# ShardedBlockCSR leaves (repro.sparse.partition): every leaf carries a
# leading shard axis, sharded over the "row_blocks" logical axis; all
# trailing dims stay local to the shard. Order mirrors
# repro.sparse.partition.SHARDED_CSR_LEAVES.
_SHARDED_CSR = {
    "values": ("row_blocks", None, None, None),
    "row_ptr": ("row_blocks", None),
    "row_id": ("row_blocks", None),
    "col_idx": ("row_blocks", None),
    "valid": ("row_blocks", None),
    "gather_index": ("row_blocks", None),
}


def _path_names(path) -> list[str]:
    out = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            out.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            out.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            out.append(k.name)
        else:
            out.append(str(k))
    return out


def _logical_axes(names: list[str], ndim: int) -> tuple:
    leaf = names[-1]
    in_period = "period" in names
    eff_ndim = ndim - 1 if in_period else ndim  # stacked leading layer dim

    if leaf in _BSR:
        spec = _BSR[leaf]
    elif "mixer" in names and leaf in _MIXER:
        spec = _MIXER[leaf]
    elif ("ffn" in names or "shared" in names) and leaf in _FFN:
        spec = _MOE_BANK[leaf] if (leaf in _MOE_BANK and eff_ndim == 3) else _FFN[leaf]
    elif leaf in _TOP:
        spec = _TOP[leaf]
    else:
        spec = (None,) * eff_ndim  # norms, scalars, unknowns → replicated
    if len(spec) != eff_ndim:
        spec = (None,) * eff_ndim  # rank mismatch (e.g. biases) → replicate
    if in_period:
        spec = (None,) + tuple(spec)
    return tuple(spec)


def _resolve_spec(
    logical: tuple, shape: tuple[int, ...], mesh: Mesh, rules: ShardingRules
) -> P:
    axes = []
    used: set[str] = set()
    for dim, lg in enumerate(logical):
        assignment = rules.resolve(lg)
        if assignment is None:
            axes.append(None)
            continue
        names = assignment if isinstance(assignment, tuple) else (assignment,)
        names = tuple(
            a for a in names if a in mesh.shape and a not in used
        )
        size = 1
        for a in names:
            size *= mesh.shape[a]
        if not names or shape[dim] % size != 0:
            axes.append(None)  # divisibility fallback → replicate this dim
            continue
        used.update(names)
        axes.append(names if len(names) > 1 else names[0])
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


# ------------------------------ public API -----------------------------------


def param_pspecs(
    cfg: ModelConfig, params: Params, mesh: Mesh, rules: ShardingRules | None = None
) -> Params:
    """PartitionSpec tree matching ``params`` (arrays or ShapeDtypeStructs)."""
    del cfg
    rules = rules or ShardingRules()

    def one(path, leaf):
        names = _path_names(path)
        return _resolve_spec(_logical_axes(names, leaf.ndim), leaf.shape, mesh, rules)

    return jax.tree_util.tree_map_with_path(one, params)


# cache leaf table: name -> logical axes (dims after the leading batch dim)
_CACHE = {
    "k": ("batch", "cache_seq", None, None),
    "v": ("batch", "cache_seq", None, None),
    "c_kv": ("batch", "cache_seq", None),
    "k_rope": ("batch", "cache_seq", None),
    "positions": (None,),
    "conv": ("batch", None, "tp"),
    "ssm": ("batch", "tp", None),
    "wkv": ("batch", "tp", None, None),
    "shift": ("batch", None),
}


def cache_pspecs(
    cfg: ModelConfig, cache: Params, mesh: Mesh, rules: ShardingRules | None = None
) -> Params:
    del cfg
    rules = rules or ShardingRules()

    def one(path, leaf):
        names = _path_names(path)
        in_period = "period" in names
        leaf_name = names[-1]
        logical = _CACHE.get(leaf_name, ("batch",) + (None,) * (leaf.ndim - 1))
        eff = leaf.ndim - 1 if in_period else leaf.ndim
        if len(logical) != eff:
            logical = (None,) * eff
        if in_period:
            logical = (None,) + tuple(logical)
        return _resolve_spec(tuple(logical), leaf.shape, mesh, rules)

    return jax.tree_util.tree_map_with_path(one, cache)


def batch_pspecs(
    mesh: Mesh, rules: ShardingRules | None = None
) -> dict[str, P]:
    """Specs for a train/serve data batch: batch dim over DP axes, optional
    sequence sharding of the token dim."""
    rules = rules or ShardingRules()
    b = tuple(a for a in rules.batch_axes if a in mesh.shape)
    s = rules.resolve("seq")
    s = s if (s is None or s in mesh.shape) else None
    return {
        "inputs": P(b, s),
        "labels": P(b, s),
    }


def shardings_for(tree, mesh: Mesh, pspecs):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ----------------------- sharded sparse weights ------------------------------


def row_block_axes(
    mesh: Mesh, rules: "ShardingRules | None" = None
) -> tuple[str, ...]:
    """Mesh axes the ``row_blocks`` logical axis lands on, in order —
    ``("row_blocks",)`` for a dedicated shard mesh, ``("data", "model")``
    style for compute meshes, ``()`` when nothing matches (unsharded)."""
    rules = rules or ShardingRules()
    assignment = rules.resolve("row_blocks") or ()
    names = assignment if isinstance(assignment, tuple) else (assignment,)
    return tuple(a for a in names if a in mesh.shape)


def mesh_shard_count(mesh: Mesh, rules: "ShardingRules | None" = None) -> int:
    """How many row-block shards this mesh carries (Π of the resolved
    ``row_blocks`` axes' sizes) — the ``n_shards`` the partitioner and
    the sharded plans must agree on."""
    n = 1
    for a in row_block_axes(mesh, rules):
        n *= mesh.shape[a]
    return n


def sharded_csr_pspecs(sharded, mesh: Mesh, rules: "ShardingRules | None" = None):
    """PartitionSpec pytree for one :class:`repro.sparse.partition.
    ShardedBlockCSR`, resolved through the same rule table as every
    other leaf (divisibility fallback included): the leading shard dim
    lands on the ``row_blocks`` axes, everything else is replicated.
    Used directly as ``shard_map`` in_specs by ``repro.plan.sharded``.
    """
    from repro.sparse.partition import SHARDED_CSR_LEAVES

    rules = rules or ShardingRules()
    leaves, treedef = jax.tree_util.tree_flatten(sharded)
    specs = [
        _resolve_spec(_SHARDED_CSR[name], leaf.shape, mesh, rules)
        for name, leaf in zip(SHARDED_CSR_LEAVES, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)
