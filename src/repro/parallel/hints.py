"""Activation sharding hints (with_sharding_constraint) via a trace-time context.

XLA's SPMD propagation does not reliably keep the data-parallel sharding on
scan carries — without pinning, the layer stack computes on replicated
activations (observed: 16x FLOP inflation on the 16x16 mesh).  Model code
calls ``hint(x, role)`` at block boundaries; the launcher activates a
(mesh, role->PartitionSpec) context around tracing.  Outside any context
the call is a no-op, so tests and single-device runs are unaffected.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx: contextvars.ContextVar = contextvars.ContextVar("shard_hints", default=None)


@contextlib.contextmanager
def use_hints(mesh: Mesh, specs: Dict[str, P]):
    token = _ctx.set((mesh, specs))
    try:
        yield
    finally:
        _ctx.reset(token)


def hint(x, role: str):
    state = _ctx.get()
    if state is None:
        return x
    mesh, specs = state
    spec = specs.get(role)
    if spec is None:
        return x
    parts = tuple(spec)
    if len(parts) < x.ndim:  # right-pad with replication
        parts = parts + (None,) * (x.ndim - len(parts))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def hint_meta(key: str, default=None):
    """Non-spec metadata carried in the hint context (e.g. SP degree)."""
    state = _ctx.get()
    if state is None:
        return default
    _, specs = state
    return specs.get(key, default)


def kv_head_mesh():
    """``(mesh, axis)`` when the active hint context shards KV heads (the
    serving engine's ``tp_kv`` role) over a mesh axis of size > 1, else
    None.  Kernels GSPMD cannot partition run under ``shard_map`` on it."""
    state = _ctx.get()
    if state is None:
        return None
    mesh, specs = state
    spec = specs.get("tp_kv")
    axis = None if spec is None or len(spec) < 3 else spec[2]
    if axis is None or mesh.shape[axis] == 1:
        return None
    return mesh, axis


def serve_hint_specs(cfg, mesh: Mesh) -> Dict[str, P]:
    """Serving-time TP hint roles (concatenation-only sharding).

    The serving engine shards only dims whose cross-shard combination is
    a *concatenation* — attention heads / KV head groups, MLP hidden,
    vocab columns — and explicitly all-gathers the sharded activations
    (``tp_gather`` / ``ffn_gather``) before the ``wo`` / ``w_down``
    contractions, so each shard's matmuls always see whole arrays.  No
    psum ever touches values, which is what keeps a TP=N token stream
    bit-identical to single-device.  These roles exist only inside the
    engine's ``use_hints`` context; ``default_hint_specs`` (training)
    never defines them, so the model-code hint sites are no-ops there.
    """
    return {
        "act": P(None, None, None),                # [B, S, D] replicated
        "tp_heads": P(None, None, "model", None),  # q [B, S, H, hd]
        "tp_kv": P(None, None, "model", None),     # k/v new [B, S, KV, hd]
        "tp_gather": P(),                          # attn out, before wo
        "ffn_hidden": P(None, None, "model"),      # g/u [B, S, F]
        "ffn_gather": P(None, None, None),         # gated h, before w_down
        "logits_decode": P(None, "model"),         # [B, Vp] decode logits
    }


def default_hint_specs(cfg, mesh: Mesh, *, batch_shardable: bool = True,
                       decode: bool = False) -> Dict[str, P]:
    from .sharding import fsdp_axes, seq_parallel, tp_size

    dp = fsdp_axes(mesh) if batch_shardable else None
    sp = seq_parallel(cfg, mesh) and not decode  # S == 1 at decode
    seq = "model" if sp else None
    return {
        "act": P(dp, seq, None),                       # [B, S, D]
        "logits": P(dp, seq, "model" if not sp else None),  # [B, S, Vp]
        "sp": tp_size(mesh) if sp else None,
    }
