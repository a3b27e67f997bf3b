"""Serving driver: two schedulers over a fixed pool of batch slots.

``--scheduler`` selects how requests reach the model:

  * ``continuous`` (default): per-step admission into free slots
    mid-flight, **chunked prefill** through the same mixed step that
    decodes the other slots (long prompts never block decode), preemption
    with page spill/restore when the pool runs dry, and per-step token
    streaming.  The state machine lives in ``serving.scheduler``; this
    module's ``Engine`` executes its decisions.  Needs ``cache-impl
    paged``.
  * ``bucketed``: the PR-2 baseline — requests admitted in prompt-length
    buckets, one blocking batched prefill per bucket, worst-case page
    reservation per request.  Kept so the continuous scheduler's wins stay
    measurable (``benchmarks/run.py serve_continuous``).

Two cache backends (``--cache-impl``):

  * ``paged`` (default): the GQA KV cache lives in a global pool of
    fixed-size FP8 pages (``repro.serving.page_pool``) shared by all slots
    and all layers — cache memory scales with the page budget, not with
    slots x max_seq.  Decode attention runs in the paper's LNS integer
    domain straight off the page codes (``kernels.paged_attention``); KV
    writes use stochastic-rounding carry-ins.  MLA latents and rope keys
    live in the pool too (the kernel's MLA mode); SSM/cross caches keep
    dense per-slot entries.
  * ``dense``: the original per-slot [slots, max_seq] cache, kept so the
    paged path's wins stay measurable (bucketed scheduler only).

Both backends drive every slot at its own position (a per-slot position
vector through ``Model.decode_step``), so slots with different history
lengths coexist in one decode batch.

``--prefix-cache on`` (paged pure-GQA caches) enables **ref-counted
prefix caching**: full prompt pages are published in a token-chunk-hash
index, requests sharing a prompt prefix map the cached pages read-only
and prefill only the uncached tail, the partial last page is cloned
copy-on-write when the cache covers a whole prompt, and unreferenced
cached pages are LRU-evicted under allocation pressure.  KV stochastic
rounding is position-addressed, so a cache hit is bit-identical to
recomputing the prefix (``docs/serving.md``).

CPU smoke scale:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --requests 6 --slots 2 --gen 16 --policy serve_fp8_paged \
      --scheduler continuous --arrival-rate 0.5 --stream
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
from collections import Counter
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import numerics
from ..caches import enable_compile_cache
from ..configs import get_config
from ..kernels.paged_attention import walked_pages
from ..models import Model
from ..serving import ContinuousScheduler, PagePool, Request
from ..serving.page_pool import invariant_checks_enabled
from ..serving.scheduler import CANCELLED, FINISHED, REJECTED, TIMED_OUT
from ..serving.telemetry import Telemetry, default_registry


def cache_bytes(tree) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


class Engine:
    # Disjoint PRNG streams for the two KV-write paths.  The seed engine
    # derived both from the same stream — ``fold_in(key, 1_000_003 +
    # step)`` for the prefill splice vs ``fold_in(key, step)`` for token
    # writes — so a long-running engine replayed prefill keys at decode
    # step ``1_000_003 + s``, biasing KV rounding.  Streams now diverge at
    # the first fold (tests/test_prefix_cache.py pins disjointness).
    # Stream 0 (token writes) is deliberately NOT folded with the engine
    # step: the attention layer folds each slot's *write position* in, so
    # page codes are a pure function of (tokens, position, layer) — the
    # property the prefix cache's bit-identity rests on.
    _STREAM_TOKEN_WRITE = 0
    _STREAM_PREFILL_SPLICE = 1

    def __init__(self, cfg, *, slots: int, max_seq: int,
                 cache_impl: str = "paged", page_size: int = 16,
                 num_pages: Optional[int] = None, rng_seed: int = 0,
                 stochastic_kv: Optional[bool] = None,
                 prefix_cache: bool = False,
                 fused_decode: bool = True,
                 telemetry: Optional[Telemetry] = None,
                 mesh=None, static_weights: bool = False):
        self.cfg = cfg
        # fused_decode=True runs decode steps as one fused KV-write+attend
        # launch; False keeps the two-launch write-then-attend composition.
        # Token streams are bit-identical either way (pinned by
        # tests/test_paged_fuzz.py), so this is a perf knob, not semantics.
        self.fused_decode = bool(fused_decode)
        # Phase spans (prefill/decode/kv_write/host) land here; the
        # scheduler shares the same registry (see ContinuousScheduler).
        self.tel = telemetry if telemetry is not None else Telemetry()
        self.model = Model(cfg, max_seq=max_seq)
        self.max_seq = max_seq
        self.slots = slots
        self.cache_impl = cache_impl
        # ``mesh``: run the engine tensor-parallel over a
        # jax.sharding.Mesh.  Weights shard concatenation-only (serve_
        # param_pspecs), activations are pinned by the serve hint roles,
        # page codes shard over the KV-head dim — token streams are
        # BIT-IDENTICAL to the mesh=None engine (tests/
        # test_serving_distributed.py).  ``static_weights`` additionally
        # quantizes eligible weights to QTensor carriers (codes sharded
        # like their weight, scales replicated); opt-in because it
        # changes the matmul path vs the plain-weight engine.
        self.mesh = mesh
        self._hint_specs = None
        self._tp = 1
        init_key = jax.random.PRNGKey(rng_seed)
        if mesh is not None:
            self._validate_mesh(cfg, mesh, cache_impl)
            from ..parallel import sharding
            from ..parallel.hints import serve_hint_specs

            self._tp = sharding.tp_size(mesh)
            self._hint_specs = serve_hint_specs(cfg, mesh)
            self._replicated = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            pol = numerics.as_policy(cfg.policy)
            param_sh = sharding.named(mesh, sharding.serve_param_pspecs(
                cfg, jax.eval_shape(self.model.init, init_key), mesh,
                policy=pol))
            # each shard initializes only its own slice of every weight
            self.params = jax.jit(self.model.init,
                                  out_shardings=param_sh)(init_key)
            if static_weights:
                from ..models.quantize import quantize_params

                self.params = quantize_params(self.params, pol,
                                              shardings=param_sh)
        else:
            self.params = self.model.init(init_key)
            if static_weights:
                from ..models.quantize import quantize_params

                self.params = quantize_params(
                    self.params, numerics.as_policy(cfg.policy))
        shape_s = ("1" if mesh is None else
                   "x".join(str(mesh.shape[a]) for a in mesh.axis_names))
        self.tel.gauge("serve_mesh_info", mesh_shape=shape_s,
                       tp_size=str(self._tp)).set(1)
        self._prefill = jax.jit(self.model.prefill)
        self._splice_cache: Dict = {}
        # stochastic-rounding KV writes only matter for FP8 caches; the
        # policy's kv_write mode carries the default
        if stochastic_kv is None:
            stochastic_kv = numerics.kv_stochastic(cfg.policy)
        self._kv_key = (
            jax.random.PRNGKey(rng_seed + 17) if stochastic_kv else None
        )
        self._token_key = (
            None if self._kv_key is None
            else jax.random.fold_in(self._kv_key, self._STREAM_TOKEN_WRITE)
        )
        self._step = 0

        self.prefix_cache = bool(prefix_cache)
        self._slot_hash: Dict[int, List[str]] = {}
        self._slot_registered: Dict[int, int] = {}
        self._cow_fn = None
        if self.prefix_cache:
            if cache_impl != "paged":
                raise ValueError("prefix caching needs cache_impl='paged'")
            if not self.prefix_cache_supported(cfg):
                raise ValueError(
                    f"prefix caching needs a pure-GQA paged KV cache; "
                    f"{cfg.name!r} (family={cfg.family!r}, "
                    f"attn_impl={cfg.attn_impl!r}) keeps cache entries "
                    "that no prefix index shares between requests"
                )
            pol = numerics.as_policy(cfg.policy)
            desc = (f"{cfg.name}|{rng_seed}|{page_size}|"
                    + ("none" if pol is None else pol.to_json()))
            # chain root of the token-chunk hashes: pages are only valid
            # across requests that share params, numerics and page layout
            self._prefix_root = hashlib.sha256(desc.encode()).digest()

        if cache_impl == "dense":
            self.pool = None
            self.cache = self.model.make_cache(slots, max_seq)
            self._decode = jax.jit(self.model.decode_step)
        elif cache_impl == "paged":
            self.page_size = page_size
            self.max_pages_per_slot = -(-max_seq // page_size)
            if num_pages is None:
                num_pages = slots * self.max_pages_per_slot + 1
            self.pool = PagePool(num_pages, page_size, slots,
                                 self.max_pages_per_slot)
            self.cache = self.place_cache(self.model.make_paged_cache(
                slots, num_pages, page_size, max_seq
            ))
            # the steps update the page pool in place (cache donated): a
            # pool sized to the device's memory cannot be held twice
            self._decode_paged = jax.jit(
                self.model.decode_step_paged,
                static_argnames=("page_size", "fused"), donate_argnums=(1,),
            )
            self._mixed_step = jax.jit(
                self.model.step_paged,
                static_argnames=("page_size", "fused"), donate_argnums=(1,),
            )
            # the attention layers (GQA or MLA) that walk the page pool
            self._paged_layers = sum(
                e["self"]["kp"].shape[0] if stacked else 1
                for stacked, entries in ((False, self.cache["prefix"]),
                                         (True, self.cache["blocks"]))
                for e in entries if "kp" in e.get("self", {}))
            # device mirror of pool.block_tables, re-uploaded only when the
            # pool's version moves (one transfer per mutating step instead
            # of one per slot per step; pinned by tests)
            self._bt_device = None
            self._bt_version = -1
        else:
            raise ValueError(f"unknown cache_impl {cache_impl!r}")
        # routing counts of the mixed steps not yet synced, fetched with
        # their logits (models with routed experts only)
        self._has_moe = cfg.n_experts > 0
        self._moe_pending: List = []

    # ------------------------------------------------------------------ #
    # Tensor-parallel mesh: validation, placement, hint context
    # ------------------------------------------------------------------ #
    @property
    def tp_size(self) -> int:
        return self._tp

    @classmethod
    def _validate_mesh(cls, cfg, mesh, cache_impl: str) -> None:
        """Mesh serving is the paged pure-GQA engine, heads sharded.

        Bit-identity needs every sharded dim to split on an exact
        head-group / ff-column / vocab-column boundary, and the paged
        cache to hold ALL attention state (dense per-slot entries would
        need their own rules); anything else is rejected up front with
        the reason, not at trace time.
        """
        if "model" not in mesh.axis_names:
            raise ValueError(
                f"serving mesh needs a 'model' axis; got {mesh.axis_names}")
        tp = mesh.shape["model"]
        extra = {a: mesh.shape[a] for a in mesh.axis_names
                 if a != "model" and mesh.shape[a] > 1}
        if extra:
            raise ValueError(
                f"serving is tensor-parallel only; non-model mesh axes "
                f"must have size 1, got {extra} (data-parallel serving "
                "replicates whole engines instead)")
        if cache_impl != "paged":
            raise ValueError("mesh serving needs cache_impl='paged'")
        if not cls.prefix_cache_supported(cfg):
            raise ValueError(
                f"mesh serving needs a pure-GQA paged cache; {cfg.name!r} "
                f"(family={cfg.family!r}, attn_impl={cfg.attn_impl!r}) "
                "keeps cache entries without TP rules (SSM states, or MLA "
                "latent pages of one KV head, which heads cannot shard)")
        for dim, what in ((cfg.n_heads, "n_heads"),
                          (cfg.n_kv_heads, "n_kv_heads"),
                          (cfg.d_ff, "d_ff"),
                          (cfg.vocab_padded, "vocab_padded")):
            if dim % tp:
                raise ValueError(
                    f"TP={tp} does not divide {what}={dim} for "
                    f"{cfg.name!r}; sharded dims must split on exact "
                    "boundaries for bit-identical serving")

    def place_cache(self, tree):
        """Attach the engine's cache sharding to ``tree`` (page codes
        over the KV-head dim, scales and dense entries replicated);
        passthrough on a single-device engine.  Snapshot restore routes
        the restored cache through here — cache leaf shapes are
        mesh-independent, so a TP=1 snapshot restores onto a TP=2 engine
        (and vice versa) byte-for-byte."""
        if self.mesh is None:
            return tree
        from ..parallel import sharding

        sh = sharding.named(self.mesh,
                            sharding.serve_cache_pspecs(tree, self.mesh))
        return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh)

    def _hints(self):
        """Hint-role context for tracing model steps on the mesh (no-op
        single-device).  with_sharding_constraint bakes at trace time, so
        every jitted model call wraps itself in this."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..parallel.hints import use_hints

        return use_hints(self.mesh, self._hint_specs)

    # ------------------------------------------------------------------ #
    # Prefix cache: chunk hashing, admission matching, COW, registration
    # ------------------------------------------------------------------ #
    @staticmethod
    def prefix_cache_supported(cfg) -> bool:
        """Prefix caching shares *pages*; it needs every block's KV state
        to live in the page pool — pure-GQA decoder-only stacks.  SSM
        states and cross/encoder caches are dense per-slot entries that
        cannot be remapped between requests; MLA's latent pages live in
        the pool, but no prefix index keys or shares them yet."""
        if cfg.family in ("vlm", "encdec") or cfg.attn_impl != "gqa":
            return False
        from ..models.transformer import layer_specs

        prefix_specs, pattern, _ = layer_specs(cfg)
        return not prefix_specs and all(s.mixer == "attn" for s in pattern)

    def _splice_key(self):
        """Per-step key of the bucketed prefill-splice rescale stream."""
        if self._kv_key is None:
            return None
        return jax.random.fold_in(
            jax.random.fold_in(self._kv_key, self._STREAM_PREFILL_SPLICE),
            self._step,
        )

    def _prompt_hashes(self, prompt: np.ndarray) -> List[str]:
        """Chained hash per FULL page of ``prompt``: hash i commits to the
        engine root (params seed, numerics policy, page size) and every
        token id up to and including page i — causal attention makes a
        page's KV a function of the whole prefix, so the chain, not the
        chunk alone, is the cache key."""
        toks = np.ascontiguousarray(np.asarray(prompt, np.int64))
        ps = self.page_size
        h = self._prefix_root
        out = []
        for i in range(len(toks) // ps):
            h = hashlib.sha256(h + toks[i * ps:(i + 1) * ps].tobytes()).digest()
            out.append(h.hex())
        return out

    def prompt_hashes(self, prompt: np.ndarray) -> List[str]:
        """Public :meth:`_prompt_hashes` ([] when the cache is off) so the
        scheduler can hash each prompt ONCE and reuse the result across
        the per-step re-plans of a budget-blocked queue head."""
        return self._prompt_hashes(prompt) if self.prefix_cache else []

    def prefix_plan(
        self, prompt: np.ndarray, hashes: Optional[List[str]] = None,
    ) -> Tuple[int, int, int, int]:
        """Read-only admission planning:
        ``(n_cached, n_mapped, extra, revived)``.

        ``n_cached`` prompt tokens can be skipped, ``n_mapped`` cached
        pages would be mapped into the slot, ``extra`` pages are drawn
        from the free pool at admission beyond the tail's own (the COW
        copy when the cache covers the whole prompt), and ``revived``
        matched pages are currently parked in the LRU — mapping them
        removes them from the allocatable set, so the admission budget
        must charge them too (an LRU-parked page counts as free until the
        request's own ``share()`` revives it).  ``hashes`` optionally
        carries the precomputed :meth:`prompt_hashes`."""
        if not self.prefix_cache:
            return 0, 0, 0, 0
        plen = int(np.asarray(prompt).shape[0])
        if hashes is None:
            hashes = self._prompt_hashes(prompt)
        ids = self.pool.match_prefix(hashes, peek=True)
        revived = sum(1 for pid in ids if self.pool.ref[pid] == 0)
        matched = len(ids) * self.page_size
        if matched and matched == plen:
            # whole prompt cached: still recompute the final token (its
            # logits seed generation), COW-ing the last matched page so
            # the recomputed write lands in an exclusive copy
            return plen - 1, len(ids), 1, revived
        return matched, len(ids), 0, revived

    def admit_prefix(self, slot: int, prompt: np.ndarray,
                     hashes: Optional[List[str]] = None) -> int:
        """Map ``prompt``'s longest cached page-prefix into ``slot``
        read-only; returns the number of prompt tokens admission skips
        (chunked prefill starts at the first uncached token).  ``hashes``
        optionally carries the precomputed :meth:`prompt_hashes`.

        When the cache covers the whole prompt, the last matched page is
        replaced by a copy-on-write clone (``PagePool.cow_page`` + a
        device copy of the page contents) and the final prompt token is
        recomputed into it — the recompute is bit-identical to the cached
        row because KV rounding streams are position-addressed."""
        if not self.prefix_cache:
            return 0
        plen = int(np.asarray(prompt).shape[0])
        if hashes is None:
            hashes = self._prompt_hashes(prompt)
        ids = self.pool.match_prefix(hashes)
        self._slot_hash[slot] = hashes
        self._slot_registered[slot] = len(ids)
        if not ids:
            return 0
        self.pool.share(slot, ids)
        matched = len(ids) * self.page_size
        if matched == plen:
            old, new = self.pool.cow_page(slot, len(ids) - 1)
            self._copy_page(old, new)
            matched = plen - 1
        return matched

    def note_prefilled(self, slot: int, n_prefilled: int) -> None:
        """Publish every prompt page ``slot`` has now fully written into
        the prefix index (schedulers call this as prefill advances)."""
        hashes = self._slot_hash.get(slot)
        if not self.prefix_cache or hashes is None:
            return
        upto = min(n_prefilled // self.page_size, len(hashes))
        start = self._slot_registered.get(slot, 0)
        for i in range(start, upto):
            self.pool.register_prefix(hashes[i], self.pool.pages_of[slot][i])
        if upto > start:
            self._slot_registered[slot] = upto

    def tail_prefill(self, admissions, *, chunk: int = 4):
        """Prefill every admission's uncached tail concurrently through
        shared masked mixed steps against the mapped cached prefixes (the
        bucketed scheduler's prefix-hit path; cache misses keep the
        batched splice prefill).

        ``admissions``: list of ``(slot, prompt, start)``.  All tails ride
        the same ``step_chunk`` calls — per-slot numerics are independent
        of batch composition, so this is bit-identical to prefilling them
        one by one, at 1/len(admissions) the model calls.  Returns
        ``{slot: final prompt token's logits row}``."""
        state = {slot: [np.asarray(prompt), int(start)]
                 for slot, prompt, start in admissions}
        out = {}
        while state:
            toks = np.zeros((self.slots, chunk), np.int32)
            lengths = np.zeros((self.slots,), np.int32)
            n_new = np.zeros((self.slots,), np.int32)
            with self.tel.span("host"):
                for slot, (prompt, done) in state.items():
                    n = min(chunk, prompt.shape[0] - done)
                    toks[slot, :n] = prompt[done:done + n]
                    lengths[slot] = done
                    n_new[slot] = n
                self.pool.ensure_capacity_batch(lengths + n_new)
            logits = self.step_chunk(toks, lengths, n_new)
            for slot in list(state):
                prompt, done = state[slot]
                done += int(n_new[slot])
                state[slot][1] = done
                self.note_prefilled(slot, done)
                if done >= prompt.shape[0]:
                    out[slot] = logits[slot]
                    del state[slot]
        return out

    def _copy_page(self, old: int, new: int) -> None:
        """Device-side COW body: copy page ``old``'s codes and scales into
        page ``new`` across every paged cache entry."""
        if self._cow_fn is None:
            def cow(cache, old, new):
                def cp(e, stacked):
                    out = {}
                    for name, v in e.items():
                        if isinstance(v, dict) and "kp" in v:
                            if stacked:
                                out[name] = {
                                    k: v[k].at[:, new].set(v[k][:, old])
                                    for k in v
                                }
                            else:
                                out[name] = {
                                    k: v[k].at[new].set(v[k][old]) for k in v
                                }
                        else:
                            out[name] = v
                    return out

                return {
                    "prefix": tuple(cp(e, False) for e in cache["prefix"]),
                    "blocks": tuple(cp(e, True) for e in cache["blocks"]),
                }

            self._cow_fn = jax.jit(cow)
        with self.tel.span("kv_write", kind="cow", src=old, dst=new):
            self.cache = self._cow_fn(self.cache, jnp.int32(old),
                                      jnp.int32(new))

    def _assert_writable(self, lengths: np.ndarray, n_new: np.ndarray) -> None:
        """Host-side guard behind the device-side write mask: every page an
        active slot will write this step must be exclusively owned — never
        a shared/cached/pinned prefix page.  One vectorized pass over the
        block tables per step (an unallocated logical page reads the null
        page 0, which is never writable, so missing capacity trips the
        assert too)."""
        lengths = np.asarray(lengths, np.int64)
        n_new = np.asarray(n_new, np.int64)
        act = n_new > 0
        if not act.any():
            return
        l0 = lengths // self.page_size
        l1 = (lengths + np.maximum(n_new, 1) - 1) // self.page_size
        logical = np.arange(self.pool.max_pages_per_slot)[None, :]
        written = act[:, None] & (logical >= l0[:, None]) & (logical <= l1[:, None])
        pids = self.pool.block_tables[written]
        bad = ~self.pool.writable_mask()[pids]
        if bad.any():
            slot_of = np.broadcast_to(
                np.arange(self.slots)[:, None], written.shape)[written]
            i = int(np.argmax(bad))
            raise AssertionError(
                f"slot {int(slot_of[i])} would write into non-exclusive "
                f"page {int(pids[i])}"
            )

    def _device_block_tables(self):
        """Device copy of the pool's block tables, re-uploaded only when
        the pool's version counter moved since the last upload — one host
        transfer per mutating step, zero for steady-state decode inside a
        page (``host_transfers_total`` counts the uploads; pinned to one
        per allocating step by tests/test_paged_serving.py)."""
        if self._bt_version != self.pool.version or self._bt_device is None:
            tables = jnp.asarray(self.pool.block_tables)
            if self.mesh is not None:
                # per-mesh upload: block tables stay host-side truth and
                # replicate to every shard in the one transfer
                tables = jax.device_put(tables, self._replicated)
            self._bt_device = tables
            self._bt_version = self.pool.version
            self.tel.counter("host_transfers_total").inc()
        return self._bt_device

    def _count_pages(self, attend: np.ndarray) -> None:
        """``paged_attn_pages_total`` of one model call, from the attend
        lengths ``attend`` [sub-steps, slots] the host holds: the pages
        the paged kernel walks and the pages a walk of every slot's
        ``max_seq`` visits."""
        walked = walked_pages(attend, self.page_size,
                              self.max_pages_per_slot, xp=np)
        self.tel.counter("paged_attn_pages_total", kind="walked").inc(
            self._paged_layers * int(walked.sum()))
        self.tel.counter("paged_attn_pages_total", kind="grid").inc(
            self._paged_layers * attend.size * self.max_pages_per_slot)

    # ------------------------------------------------------------------ #
    def _prefill_batch_inputs(self, prompts: List[np.ndarray]):
        cfg = self.cfg
        toks = jnp.asarray(np.stack(prompts), jnp.int32)
        batch = {"tokens": toks}
        n = len(prompts)
        if cfg.family == "vlm":
            batch["img"] = jnp.zeros(
                (n, cfg.n_img_tokens, cfg.d_model), jnp.float32
            )
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (n, cfg.enc_context, cfg.d_model), jnp.float32
            )
        return batch

    def _splice_fn(self, n: int, plen_total: int):
        """Jitted splice of an n-row prefill cache into slots/pages.

        Cached per (n, plen_total) — prompt lengths are bucketed by the
        caller, so the trace count stays small.
        """
        key = (n, plen_total)
        if key in self._splice_cache:
            return self._splice_cache[key]
        cfg = self.cfg
        paged = self.cache_impl == "paged"
        npages = self.pool.pages_needed(plen_total) if paged else 0

        def splice_dense_leaf(big, new, slot_ids, stacked: bool):
            """Write each prefill row into its slot of a dense cache leaf."""
            batch_ax = 1 if stacked else 0
            for i in range(n):
                row = jax.lax.index_in_dim(new, i, axis=batch_ax, keepdims=True)
                starts = [jnp.int32(0)] * big.ndim
                starts[batch_ax] = slot_ids[i]
                big = jax.lax.dynamic_update_slice(
                    big, row.astype(big.dtype), tuple(starts)
                )
            return big

        def splice_entry(c_e, s_e, slot_ids, page_ids, keys, stacked: bool):
            out = {}
            for name, cv in c_e.items():
                if isinstance(cv, dict) and "kp" in cv:
                    # paged entry: quantize the prefill rows into pages
                    # (fmt/mode resolved from the numerics policy); MLA's
                    # rope keys go to "kp" pages and its latents to "vp"
                    def wr(pages, scales, src, pids, k):
                        return numerics.kv_write_prefill(
                            cfg.policy, pages, scales, src, pids, key=k,
                        )

                    kp, ks = cv["kp"], cv["ks"]
                    vp, vs = cv["vp"], cv["vs"]
                    src = s_e[name]
                    k_src, v_src = ((src["kpe"], src["ckv"]) if "ckv" in src
                                    else (src["k"], src["v"]))
                    for i in range(n):
                        ki = None if keys is None else jax.random.fold_in(keys, 2 * i)
                        vi = None if keys is None else jax.random.fold_in(keys, 2 * i + 1)
                        if stacked:  # [NB, ...] arrays: vmap the page write
                            nb = kp.shape[0]
                            kis = None if ki is None else jax.random.split(ki, nb)
                            vis = None if vi is None else jax.random.split(vi, nb)
                            vwr = jax.vmap(wr, in_axes=(0, 0, 0, None, None if ki is None else 0))
                            kp, ks = vwr(kp, ks, k_src[:, i], page_ids[i], kis)
                            vp, vs = vwr(vp, vs, v_src[:, i], page_ids[i], vis)
                        else:
                            kp, ks = wr(kp, ks, k_src[i], page_ids[i], ki)
                            vp, vs = wr(vp, vs, v_src[i], page_ids[i], vi)
                    out[name] = {"kp": kp, "vp": vp, "ks": ks, "vs": vs}
                elif isinstance(cv, dict):
                    out[name] = {
                        k: splice_dense_leaf(cv[k], s_e[name][k], slot_ids, stacked)
                        for k in cv
                    }
                else:
                    out[name] = splice_dense_leaf(cv, s_e[name], slot_ids, stacked)
            return out

        def splice(cache, small, slot_ids, page_ids, keys):
            new_prefix = tuple(
                splice_entry(c, s, slot_ids, page_ids, keys, stacked=False)
                for c, s in zip(cache["prefix"], small["prefix"])
            )
            new_blocks = tuple(
                splice_entry(c, s, slot_ids, page_ids, keys, stacked=True)
                for c, s in zip(cache["blocks"], small["blocks"])
            )
            return {"prefix": new_prefix, "blocks": new_blocks}

        jitted = jax.jit(splice)
        self._splice_cache[key] = (jitted, npages)
        return self._splice_cache[key]

    def prefill_batch(self, prompts: List[np.ndarray], slots: List[int]):
        """Batched prefill admission: one model call for all new requests,
        then splice each row's cache into its slot (pages or dense rows).
        Returns (first_tokens [n], plen_total)."""
        cfg = self.cfg
        n = len(prompts)
        plen = prompts[0].shape[0]
        assert all(p.shape[0] == plen for p in prompts), "bucket by length"
        img_off = cfg.n_img_tokens if cfg.family == "vlm" else 0
        plen_total = plen + img_off
        with self.tel.span("prefill", n=n, plen=plen_total), self._hints():
            logits, small = self._prefill(
                self.params, self._prefill_batch_inputs(prompts)
            )
        with self.tel.span("host"):
            splice, npages = self._splice_fn(n, plen_total)
            if self.cache_impl == "paged":
                page_ids = np.zeros((n, npages), np.int32)
                for i, slot in enumerate(slots):
                    page_ids[i] = self.pool.alloc(slot, npages)
            else:
                page_ids = np.zeros((n, 1), np.int32)
        # NOTE: splice-written page codes are step/batch-addressed (the
        # splice stream folds the engine step), NOT content-pure, so they
        # are never registered in the prefix index — with the prefix cache
        # on, run_bucketed routes every admission through the
        # position-addressed chunked pipeline instead of this path.
        with self.tel.span("kv_write", kind="splice", n=n, plen=plen_total):
            self.cache = splice(
                self.cache, small, jnp.asarray(np.asarray(slots, np.int32)),
                jnp.asarray(page_ids), self._splice_key(),
            )
        first = np.argmax(np.asarray(logits[:, : cfg.vocab]), axis=-1)
        return first, plen_total

    # ------------------------------------------------------------------ #
    def decode(self, tokens: np.ndarray, pos: np.ndarray):
        """Dense decode step; ``pos`` is the per-slot position vector."""
        with self.tel.span("decode"):
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32),
            )
            self._step += 1
            return self.sync_logits(logits[:, : self.cfg.vocab])

    def sync_logits(self, logits) -> np.ndarray:
        """Block on an async-dispatched step's logits (the token-emission
        boundary); no-op passthrough for an already-host array.  On a
        mesh this wait also covers the step's collectives (the all-gather
        hints and the sharded-logits device->host gather), so the span is
        named ``collectives`` there — BENCH phase breakdowns attribute
        the cross-shard cost to one row.  Inside it, ``wait`` is the host
        blocked while the device computes and ``fetch`` the copy of the
        logits to the host, together with the routing counts of the mixed
        steps since the last fetch (models with routed experts), which
        feed ``moe_assignments_total`` and the fetch span's args."""
        if isinstance(logits, np.ndarray):
            return logits
        with self.tel.span("collectives" if self._tp > 1 else "sync"):
            with self.tel.span("wait"):
                jax.block_until_ready(logits)
            with self.tel.span("fetch") as args:
                pending = getattr(self, "_moe_pending", None)
                if not pending:
                    return np.asarray(logits)
                host, counts = jax.device_get((logits, pending))
                self._moe_pending = []
                held, absent = (int(n) for n in np.sum(counts, axis=0))
                self.tel.counter("moe_assignments_total",
                                 expert="held").inc(held)
                self.tel.counter("moe_assignments_total",
                                 expert="absent").inc(absent)
                args.update(moe_held=held, moe_absent=absent)
                return host

    def decode_paged(self, tokens: np.ndarray, lengths: np.ndarray, *,
                     sync: bool = True):
        """Paged decode step; allocates fresh pages for slots crossing a
        page boundary, then runs the paged decode.  Slots with ``lengths
        == 0`` are idle: their writes are masked into the null page (the
        explicit write-mask convention), so a slot whose block table still
        maps shared prefix pages cannot corrupt them.

        ``sync=False`` returns the device logits without blocking (JAX
        async dispatch): the caller overlaps host bookkeeping with the
        device step and calls :meth:`sync_logits` at the token-emission
        boundary."""
        lengths = np.asarray(lengths)
        active = lengths > 0
        with self.tel.span("host"):
            self.pool.ensure_capacity_batch(np.where(active, lengths + 1, 0))
            self._assert_writable(lengths, active.astype(np.int32))
            tables = self._device_block_tables()
            self._count_pages(lengths[None] + 1)
        with self.tel.span("decode"), self._hints():
            logits, self.cache = self._decode_paged(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lengths, jnp.int32), tables,
                page_size=self.page_size, key=self._token_key,
                active=jnp.asarray(active), fused=self.fused_decode,
            )
            self._step += 1
            out = logits[:, : self.cfg.vocab]
        if not sync:
            return out
        return self.sync_logits(out)

    def step_chunk(self, tokens: np.ndarray, lengths: np.ndarray,
                   n_new: np.ndarray, *, sync: bool = True):
        """Mixed prefill+decode step (continuous scheduler and the
        bucketed prefix-hit tail prefill).

        tokens: [slots, T]; lengths/n_new: [slots].  Slots with ``n_new >
        1`` consume a prefill chunk, ``n_new == 1`` decode one token,
        ``n_new == 0`` idle (write-masked into the null page).  The caller
        has already allocated pages for ``lengths + n_new`` tokens per
        slot, and every page written must be exclusively owned — shared
        prefix pages are read-only (checked host-side here, masked
        device-side in the model).  Returns each slot's last-valid-token
        logits [slots, vocab] — the live device array when ``sync=False``
        (resolve with :meth:`sync_logits` at the emission boundary).
        """
        with self.tel.span("host"):
            lengths, n_new = np.asarray(lengths), np.asarray(n_new)
            self._assert_writable(lengths, n_new)
            tables = self._device_block_tables()
            # sub-step t attends at lengths + min(t, n_new - 1) + 1
            t = np.arange(np.shape(tokens)[1])[:, None]
            self._count_pages(lengths + np.minimum(t, np.maximum(n_new - 1, 0))
                              + 1)
        # a step carrying any prefill chunk is charged to "prefill" (the
        # chunk dominates its T=chunk trace); pure decode steps to "decode"
        phase = "decode" if all(int(n) <= 1 for n in n_new) else "prefill"
        with self.tel.span(phase), self._hints():
            logits, self.cache, counts = self._mixed_step(
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(n_new, jnp.int32), tables,
                page_size=self.page_size, key=self._token_key,
                fused=self.fused_decode,
            )
            if self._has_moe:
                self._moe_pending.append(counts)
            self._step += 1
            out = logits[:, : self.cfg.vocab]
        if not sync:
            return out
        return self.sync_logits(out)

    # ------------------------------------------------------------------ #
    def _map_entries(self, fn):
        """Apply ``fn(entry, stacked)`` over every cache entry, rebuilding
        the cache pytree (prefix entries are unstacked; block entries carry
        a leading n_blocks axis)."""
        return {
            "prefix": tuple(fn(e, False) for e in self.cache["prefix"]),
            "blocks": tuple(fn(e, True) for e in self.cache["blocks"]),
        }

    def preempt_slot(self, slot: int) -> dict:
        """Spill ``slot`` to the host: copy its *exclusive* page codes +
        scales out of every paged entry and its per-slot rows out of every
        dense entry (SSM states), then free those pages.
        Shared/registered prefix pages are neither copied nor freed — they
        stay resident under a pin (``PagePool.spill_slot``) and are
        re-referenced on restore, so preempting a reader of a shared
        system prompt moves no bytes for the shared pages.  The copies are
        verbatim — never re-quantized — so a later :meth:`restore_slot` is
        bit-identical.  Returns the spill record."""
        with self.tel.span("preempt", slot=slot):
            return self._preempt_slot(slot)

    def _preempt_slot(self, slot: int) -> dict:
        spilled, pinned = self.pool.spill_plan(slot)
        ids = jnp.asarray(np.asarray(spilled, np.int32))

        def gather(e, stacked):
            out = {}
            for name, v in e.items():
                if isinstance(v, dict) and "kp" in v:
                    ax = 1 if stacked else 0
                    out[name] = {k: jnp.take(v[k], ids, axis=ax) for k in v}
                elif isinstance(v, dict):
                    out[name] = {
                        k: (v[k][:, slot] if stacked else v[k][slot])
                        for k in v
                    }
                else:
                    out[name] = v[:, slot] if stacked else v[slot]
            return out

        state = jax.device_get(self._map_entries(gather))
        self.pool.spill_slot(slot)
        return {
            "n_pages": len(spilled), "pinned": pinned, "state": state,
            "hashes": self._slot_hash.pop(slot, None),
            "registered": self._slot_registered.pop(slot, 0),
        }

    def restore_slot(self, slot: int, record: dict) -> None:
        """Re-admit a preempted request into ``slot``: allocate fresh pages
        for the exclusive contents (ids may differ from the spilled ones),
        scatter the saved codes, scales and dense rows back, and
        re-reference the pinned prefix pages at their logical indices."""
        with self.tel.span("restore", slot=slot):
            self._restore_slot(slot, record)

    def _restore_slot(self, slot: int, record: dict) -> None:
        new_ids = self.pool.restore_slot(
            slot, record["n_pages"], record.get("pinned", ())
        )
        if record.get("hashes") is not None:
            self._slot_hash[slot] = record["hashes"]
            self._slot_registered[slot] = record.get("registered", 0)
        ids = jnp.asarray(np.asarray(new_ids, np.int32))
        saved = record["state"]
        which = {"i": 0}

        def scatter(e, stacked):
            s = saved["blocks" if stacked else "prefix"][which["i"]]
            out = {}
            for name, v in e.items():
                if isinstance(v, dict) and "kp" in v:
                    out[name] = {
                        k: (v[k].at[:, ids].set(s[name][k]) if stacked
                            else v[k].at[ids].set(s[name][k]))
                        for k in v
                    }
                elif isinstance(v, dict):
                    out[name] = {
                        k: (v[k].at[:, slot].set(s[name][k]) if stacked
                            else v[k].at[slot].set(s[name][k]))
                        for k in v
                    }
                else:
                    out[name] = (v.at[:, slot].set(s[name]) if stacked
                                 else v.at[slot].set(s[name]))
            return out

        prefix = []
        for e in self.cache["prefix"]:
            which["i"] = len(prefix)
            prefix.append(scatter(e, False))
        blocks = []
        for e in self.cache["blocks"]:
            which["i"] = len(blocks)
            blocks.append(scatter(e, True))
        self.cache = {"prefix": tuple(prefix), "blocks": tuple(blocks)}

    def release(self, slot: int):
        if self.pool is not None:
            self.pool.free_slot(slot)
        self._slot_hash.pop(slot, None)
        self._slot_registered.pop(slot, None)

    # ------------------------------------------------------------------ #
    def kv_cache_bytes(self) -> int:
        return cache_bytes(self.cache)

    def kv_capacity_tokens(self) -> int:
        """Token capacity the cache memory buys (pool pages or dense rows)."""
        if self.pool is not None:
            return (self.pool.num_pages - 1) * self.page_size
        return self.slots * self.max_seq


def sample(logits: np.ndarray, temperature: float, rng: np.random.Generator):
    if temperature <= 0:
        return logits.argmax(-1)
    z = logits / temperature
    z = z - z.max(-1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(-1, keepdims=True)
    return np.array([rng.choice(len(row), p=row) for row in p])


def run(eng: Engine, queue: List[np.ndarray], *, gen: int,
        temperature: float = 0.0, seed: int = 0, quiet: bool = False,
        scheduler: str = "bucketed", arrivals=None, chunk: int = 4,
        on_token=None, deadline_steps: Optional[int] = None,
        deadline_s: Optional[float] = None,
        max_tokens: Optional[int] = None, max_queue: Optional[int] = None,
        watermark_high: float = 1.0, watermark_low: float = 0.75,
        control=None):
    """Serve ``queue`` to completion.  Returns (outputs, stats).

    ``scheduler``: "bucketed" (batched length-bucket prefills, worst-case
    page reservation) or "continuous" (chunked prefill + preemption, paged
    cache only).  ``arrivals`` optionally gives each request's arrival step
    (Poisson-stream simulation); ``on_token(rid, token, step)`` streams
    tokens as they are sampled.

    Fault isolation (both schedulers): a request that cannot be served is
    terminated *individually* — its pages released, pool invariants intact
    — and ``stats["statuses"]`` records every request's terminal state and
    reason; ``outputs`` holds only FINISHED requests.  ``deadline_steps``/
    ``deadline_s`` bound each request's scheduler-step/wall-clock budget,
    ``max_tokens`` caps generation, ``control`` (a
    :class:`~repro.serving.ServeControl`) cancels individual rids
    mid-flight.  ``max_queue`` and the watermark pair add admission
    backpressure (continuous scheduler only).
    """
    if scheduler == "continuous":
        return run_continuous(eng, queue, gen=gen, temperature=temperature,
                              seed=seed, quiet=quiet, arrivals=arrivals,
                              chunk=chunk, on_token=on_token,
                              deadline_steps=deadline_steps,
                              deadline_s=deadline_s, max_tokens=max_tokens,
                              max_queue=max_queue,
                              watermark_high=watermark_high,
                              watermark_low=watermark_low, control=control)
    if scheduler != "bucketed":
        raise ValueError(f"unknown scheduler {scheduler!r}")
    return run_bucketed(eng, queue, gen=gen, temperature=temperature,
                        seed=seed, quiet=quiet, arrivals=arrivals,
                        chunk=chunk, on_token=on_token,
                        deadline_steps=deadline_steps,
                        deadline_s=deadline_s, max_tokens=max_tokens,
                        control=control)


def run_bucketed(eng: Engine, queue: List[np.ndarray], *, gen: int,
                 temperature: float = 0.0, seed: int = 0, quiet: bool = False,
                 arrivals=None, chunk: int = 4, on_token=None,
                 deadline_steps: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 max_tokens: Optional[int] = None, control=None):
    """Bucketed-admission loop over ``queue`` (the PR-2 baseline).
    Returns (outputs, stats).

    Per-request fault isolation: an oversized request (worst case bigger
    than the whole pool or one slot's block table) is REJECTED at its
    admission attempt — earlier admissions in the same bucket keep their
    slots and pages — and deadline-blown (``deadline_steps`` steps or
    ``deadline_s`` seconds from arrival) or cancelled requests release
    their slot individually.  ``stats["statuses"]`` records every
    request's terminal state."""
    rng = np.random.default_rng(seed)
    if max_tokens is not None:
        gen = min(gen, max_tokens)
    requests = len(queue)
    img_off = eng.cfg.n_img_tokens if eng.cfg.family == "vlm" else 0
    active: Dict[int, dict] = {}
    reserved: Dict[int, int] = {}  # slot -> worst-case page reservation
    outputs: Dict[int, list] = {}
    statuses: Dict[int, tuple] = {}  # rid -> (terminal state, reason)
    terminal = Counter()
    next_req = 0
    tel = eng.tel
    clock = tel.clock  # monotonic: elapsed-time math must not see wall
    t0 = clock()       # clock jumps (NTP slew, DST)
    steps = 0
    decoded_tokens = 0
    decode_wall_s = 0.0  # pure-decode device time (decode-only tok/s)
    occupied_slot_steps = 0
    prefix_hit_tokens = 0

    def finish(rid: int, state: str, reason: str = "") -> None:
        statuses[rid] = (state, reason)
        terminal[state] += 1
        tel.counter("serve_requests_total", state=state).inc()

    def arrival_of(rid: int) -> int:
        return 0 if arrivals is None else int(arrivals[rid])

    def expired(rid: int) -> Optional[str]:
        if control is not None and control.cancelled(rid):
            return CANCELLED
        if (deadline_steps is not None
                and steps - arrival_of(rid) >= deadline_steps):
            return TIMED_OUT
        if deadline_s is not None and clock() - t0 > deadline_s:
            return TIMED_OUT
        return None

    while len(statuses) < requests:
        # one ``step`` span per loop pass: admission, its prefills and
        # (when a slot is active) one decode step (T=1)
        with tel.span("step", n=steps, T=0) as step:
            with tel.span("admit"):
                # ---- deadline/cancellation sweep over the active slots ---- #
                for slot, st in list(active.items()):
                    state = expired(st["rid"])
                    if state is not None:
                        finish(st["rid"], state,
                               "cancelled by client" if state == CANCELLED
                               else "deadline exhausted")
                        del active[slot]
                        reserved.pop(slot, None)
                        eng.release(slot)
                # ---- batched admission into every free slot --------------- #
                # Admission control reserves each request's worst-case page
                # count (prompt + full generation budget) so decode can never
                # exhaust the pool mid-flight; pages themselves are still
                # allocated lazily.  With the prefix cache on, the reservation
                # stays the conservative full worst case (shared pages
                # double-count, never under-count), and EVERY admission — hit
                # or miss — prefills through the position-addressed chunked
                # pipeline (Engine.tail_prefill, start = matched length):
                # registered pages must be content-pure, which the step-keyed
                # batched splice cannot provide.  Hits map their cached pages
                # read-only and prefill only the uncached tail.
                admit_slots, admit_prompts, admit_rids = [], [], []
                chunked_admissions = []  # (slot, rid, prompt, n_cached)
                for slot in range(eng.slots):
                    if slot in active:
                        continue
                    # Drain terminal queue heads before admitting into this
                    # slot: already-cancelled/expired requests, and requests
                    # whose worst case cannot fit an EMPTY pool (or one slot's
                    # block table) — each is terminated *individually*,
                    # holding no slot or pages, instead of crashing the run
                    # with earlier admissions' pages already taken.
                    while next_req < requests:
                        if arrivals is not None and arrivals[next_req] > steps:
                            break  # FIFO: the next request has not arrived yet
                        state = expired(next_req)
                        if state is not None:
                            finish(next_req, state,
                                   "cancelled by client" if state == CANCELLED
                                   else "deadline exhausted before admission")
                            next_req += 1
                            continue
                        if eng.pool is not None:
                            worst = eng.pool.pages_needed(
                                queue[next_req].shape[0] + img_off + gen
                            )
                            usable = min(eng.pool.num_pages - 1,
                                         eng.pool.max_pages_per_slot)
                            if worst > usable:
                                finish(next_req, REJECTED,
                                       f"needs {worst} pages but the pool "
                                       f"serves at most {usable} per request; "
                                       f"raise --pages or lower "
                                       f"--gen/--prompt-len")
                                next_req += 1
                                if invariant_checks_enabled():
                                    eng.pool.assert_invariants()
                                continue
                        break
                    if next_req >= requests:
                        break
                    if arrivals is not None and arrivals[next_req] > steps:
                        break  # FIFO: the next request has not arrived yet
                    prompt = queue[next_req]
                    if eng.pool is not None:
                        worst = eng.pool.pages_needed(
                            prompt.shape[0] + img_off + gen)
                        if (sum(reserved.values()) + worst
                                > eng.pool.num_pages - 1):
                            break  # wait for in-flight requests to free pages
                        reserved[slot] = worst
                    n_cached = eng.admit_prefix(slot, prompt)
                    if eng.prefix_cache:
                        chunked_admissions.append(
                            (slot, next_req, prompt, n_cached))
                    else:
                        admit_slots.append(slot)
                        admit_prompts.append(prompt)
                        admit_rids.append(next_req)
                    next_req += 1
            if admit_prompts:
                # bucket by prompt length: each bucket is one batched prefill
                by_len: Dict[int, List[int]] = {}
                for i, p in enumerate(admit_prompts):
                    by_len.setdefault(p.shape[0], []).append(i)
                for idxs in by_len.values():
                    first, plen_total = eng.prefill_batch(
                        [admit_prompts[i] for i in idxs],
                        [admit_slots[i] for i in idxs],
                    )
                    for j, i in enumerate(idxs):
                        active[admit_slots[i]] = dict(
                            rid=admit_rids[i], pos=plen_total,
                            out=[int(first[j])], last=int(first[j]),
                        )
                        if on_token is not None:
                            on_token(admit_rids[i], int(first[j]), steps)
            if chunked_admissions:
                rows = eng.tail_prefill(
                    [(slot, prompt, n_cached)
                     for slot, _, prompt, n_cached in chunked_admissions],
                    chunk=chunk,
                )
                for slot, rid, prompt, n_cached in chunked_admissions:
                    first = int(np.argmax(rows[slot][: eng.cfg.vocab]))
                    prefix_hit_tokens += n_cached
                    tel.counter("serve_prefix_hit_tokens_total").inc(n_cached)
                    active[slot] = dict(rid=rid, pos=prompt.shape[0] + img_off,
                                        out=[first], last=first)
                    if on_token is not None:
                        on_token(rid, first, steps)

            if not active:
                # nothing in flight (requests still arriving): let time pass
                steps += 1
                if eng.pool is not None:
                    eng.pool.observe_step()
                continue

            # ---- one decode step for the whole pool ----------------------- #
            step["T"] = 1
            toks = np.zeros((eng.slots,), np.int32)
            pos = np.zeros((eng.slots,), np.int32)
            for slot, st in active.items():
                toks[slot] = st["last"]
                pos[slot] = st["pos"]
            t_dec = clock()
            if eng.cache_impl == "paged":
                # async dispatch: per-step counters and pool telemetry run on
                # the host while the device decodes; sync_logits blocks at the
                # sampling (token-emission) boundary below
                logits = eng.decode_paged(toks, pos, sync=False)
            else:
                logits = eng.decode(toks, pos)
            steps += 1
            tel.counter("serve_steps_total").inc()
            decoded_tokens += len(active)
            tel.counter("serve_decoded_tokens_total").inc(len(active))
            occupied_slot_steps += len(active)
            if eng.pool is not None:
                eng.pool.observe_step()
                eng.pool.publish_telemetry(tel)
            logits = eng.sync_logits(logits)
            decode_wall_s += clock() - t_dec
            nxt = sample(logits, temperature, rng)
            done = []
            for slot, st in list(active.items()):
                st["last"] = int(nxt[slot])
                st["out"].append(st["last"])
                st["pos"] += 1
                if on_token is not None:
                    on_token(st["rid"], st["last"], steps)
                if len(st["out"]) >= gen:
                    outputs[st["rid"]] = st["out"]
                    finish(st["rid"], FINISHED)
                    done.append(slot)
            for slot in done:
                del active[slot]
                reserved.pop(slot, None)
                eng.release(slot)
            if invariant_checks_enabled() and eng.pool is not None:
                eng.pool.assert_invariants()

    dt = clock() - t0
    stats = dict(
        steps=steps, wall_s=dt,
        # end-to-end throughput (prefill + admission + host time folded
        # in) vs decode-only throughput (device decode-step time alone)
        tok_s=decoded_tokens / dt if dt > 0 else 0.0,
        decode_tok_s=(decoded_tokens / decode_wall_s
                      if decode_wall_s > 0 else 0.0),
        decode_wall_s=decode_wall_s,
        slot_occupancy=occupied_slot_steps / max(steps * eng.slots, 1),
        preemptions=0,
        shed=0,
        terminal=dict(terminal),
        statuses=statuses,
        prefix_hit_tokens=prefix_hit_tokens,
        cache_bytes=eng.kv_cache_bytes(),
        cache_bytes_per_token=eng.kv_cache_bytes() / max(eng.kv_capacity_tokens(), 1),
        phases=tel.phase_seconds(),
        telemetry=tel,
    )
    if eng.pool is not None:
        stats["page_utilization"] = eng.pool.mean_utilization()
        stats["prefix"] = eng.pool.prefix_stats()
    if not quiet:
        print(f"[serve:bucketed:{eng.cache_impl}] {requests} requests, "
              f"{steps} decode steps, {stats['tok_s']:.1f} tok/s e2e "
              f"({stats['decode_tok_s']:.1f} decode-only), "
              f"occupancy {stats['slot_occupancy']:.2f}, cache "
              f"{stats['cache_bytes'] / 1e6:.2f} MB "
              f"({stats['cache_bytes_per_token']:.0f} B/token capacity)")
    return outputs, stats


def run_continuous(eng: Engine, queue: List[np.ndarray], *, gen: int,
                   temperature: float = 0.0, seed: int = 0,
                   quiet: bool = False, arrivals=None, chunk: int = 4,
                   on_token=None, deadline_steps: Optional[int] = None,
                   deadline_s: Optional[float] = None,
                   max_tokens: Optional[int] = None,
                   max_queue: Optional[int] = None,
                   watermark_high: float = 1.0, watermark_low: float = 0.75,
                   control=None):
    """Continuous-batching loop: chunked prefill, mid-flight joins,
    preemption with page spill/restore, per-step streaming.  Returns
    (outputs, stats); the lifecycle/backpressure kwargs are documented on
    :func:`run`."""
    if eng.cache_impl != "paged":
        raise ValueError(
            "the continuous scheduler drives the paged engine; rerun with "
            "cache_impl='paged' (dense caches use scheduler='bucketed')"
        )
    if eng.cfg.family in ("vlm", "encdec"):
        raise ValueError(
            f"continuous scheduling needs decode-only prefill, which the "
            f"{eng.cfg.family!r} family's prefix inputs (image/encoder "
            "context) do not support; use scheduler='bucketed'"
        )
    rng = np.random.default_rng(seed)

    def sample_row(row: np.ndarray) -> int:
        return int(sample(row[None], temperature, rng)[0])

    sched = ContinuousScheduler(eng, chunk=chunk, sample=sample_row,
                                on_token=on_token, control=control,
                                max_tokens=max_tokens, max_queue=max_queue,
                                watermark_high=watermark_high,
                                watermark_low=watermark_low)
    for i, prompt in enumerate(queue):
        sched.add(Request(
            rid=i, prompt=np.asarray(prompt), gen=gen,
            arrival=0 if arrivals is None else int(arrivals[i]),
            deadline_steps=deadline_steps, deadline_s=deadline_s,
        ))
    tel = sched.tel
    t0 = tel.clock()  # monotonic (elapsed math must not see wall jumps)
    outputs = sched.run()
    dt = tel.clock() - t0
    stats = dict(
        steps=sched.steps, wall_s=dt,
        # end-to-end throughput (prefill + admission + host time folded
        # in) vs decode-only throughput (device time of pure-decode
        # steps; the ambiguity satellite in BENCH_4's prefix-ON number)
        tok_s=sched.decoded_tokens / dt if dt > 0 else 0.0,
        decode_tok_s=(sched.decode_step_tokens / sched.decode_wall_s
                      if sched.decode_wall_s > 0 else 0.0),
        decode_wall_s=sched.decode_wall_s,
        prefill_wall_s=sched.prefill_wall_s,
        prefill_tokens=sched.prefill_tokens,
        prefix_hit_tokens=sched.prefix_hit_tokens,
        prefix=eng.pool.prefix_stats(),
        slot_occupancy=sched.occupied_slot_steps / max(sched.steps * eng.slots, 1),
        mean_latency_steps=sched.mean_latency_steps(),
        preemptions=sched.preemptions,
        restores=sched.restores,
        shed=sched.shed,
        admission_pauses=sched.admission_pauses,
        terminal=dict(sched.terminal_counts),
        statuses=sched.statuses(),
        requests=sched.request_traces(),
        page_utilization=eng.pool.mean_utilization(),
        cache_bytes=eng.kv_cache_bytes(),
        cache_bytes_per_token=eng.kv_cache_bytes() / max(eng.kv_capacity_tokens(), 1),
        phases=tel.phase_seconds(),
        telemetry=tel,
    )
    if not quiet:
        print(f"[serve:continuous:{eng.cache_impl}] {len(queue)} requests, "
              f"{sched.steps} steps, {stats['tok_s']:.1f} tok/s e2e "
              f"({stats['decode_tok_s']:.1f} decode-only), occupancy "
              f"{stats['slot_occupancy']:.2f}, {sched.preemptions} "
              f"preemptions, cache {stats['cache_bytes'] / 1e6:.2f} MB "
              f"({stats['cache_bytes_per_token']:.0f} B/token capacity)")
    return outputs, stats


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve random prompts through the paged LNS engine.",
        epilog="Schedulers: 'continuous' (chunked prefill, mid-flight "
               "joins, preemption with page spill/restore; paged cache "
               "only) or 'bucketed' (batched length-bucket prefills, "
               "worst-case page reservation; paged or dense cache).",
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default=None,
                    help="named numerics policy preset (e.g. "
                         "serve_fp8_paged, train_bf16; see "
                         "repro.numerics.available_policies())")
    ap.add_argument("--quant", default=None,
                    help="DEPRECATED alias for --policy; legacy flat "
                         "quant flag, mapped through "
                         "QuantConfig.to_policy()")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "bucketed"],
                    help="admission policy (default: continuous)")
    ap.add_argument("--cache-impl", default="paged",
                    choices=["paged", "dense"])
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size (0 = worst-case slots*max_seq)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="ref-counted prefix caching: requests sharing a "
                         "prompt prefix reuse its KV pages and prefill "
                         "only the uncached tail (paged pure-GQA caches)")
    ap.add_argument("--fused-decode", default="on", choices=["on", "off"],
                    help="fuse the token KV write into the paged decode "
                         "attention (one launch per step); 'off' keeps "
                         "the write-then-attend composition.  Token "
                         "streams are bit-identical either way")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", default="8",
                    help="prompt length, or a comma list cycled over the "
                         "requests for a mixed-length stream (e.g. 4,12,8)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared tokens to every prompt "
                         "(a common system prompt; the prefix-cache "
                         "workload)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=4,
                    help="prefill tokens per step per slot (continuous)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean request arrivals per step for a simulated "
                         "Poisson stream (0 = everything queued at step 0)")
    ap.add_argument("--stream", action="store_true",
                    help="print each token the step it is sampled")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request scheduler-step budget from arrival "
                         "(0 = unbounded); blown deadlines time the "
                         "request out individually")
    ap.add_argument("--max-tokens", type=int, default=0,
                    help="hard cap on any request's generation budget "
                         "(0 = uncapped)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on arrived-but-unadmitted requests; "
                         "overflow is load-shed (continuous scheduler; "
                         "0 = unbounded)")
    ap.add_argument("--watermark-high", type=float, default=1.0,
                    help="page-pool occupancy fraction that pauses new "
                         "admissions (continuous scheduler)")
    ap.add_argument("--watermark-low", type=float, default=0.75,
                    help="occupancy fraction that resumes admissions")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="serve tensor-parallel over a device mesh, e.g. "
                         "1x2 (the model axis shards attention heads / "
                         "MLP / vocab).  Token streams are bit-identical "
                         "to the single-device engine.  Needs that many "
                         "devices (XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N for "
                         "host testing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final Prometheus text exposition "
                         "(counters/gauges/histograms; see "
                         "docs/observability.md) to PATH")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of every "
                         "phase span to PATH (open in chrome://tracing)")
    ap.add_argument("--profile-spans", action="store_true",
                    help="wrap each phase span in a "
                         "jax.profiler.TraceAnnotation so host phases "
                         "line up with device traces")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.policy is not None:
        if args.quant not in (None, "none"):
            ap.error("--policy and the deprecated --quant are exclusive")
        cfg = get_config(args.arch, smoke=args.smoke, policy=args.policy)
    else:
        quant = args.quant or "none"
        if quant != "none":
            from ..numerics import LEGACY_QUANT_PRESETS

            print(f"# --quant {quant} is deprecated; use --policy "
                  f"{LEGACY_QUANT_PRESETS.get(quant, '<custom>')} "
                  "(mapped through QuantConfig.to_policy())")
        cfg = get_config(args.arch, smoke=args.smoke, quant=quant)
    if args.scheduler == "continuous" and (
        args.cache_impl == "dense" or cfg.family in ("vlm", "encdec")
    ):
        print("# continuous scheduling needs a paged cache and decode-only "
              "prefill; falling back to the bucketed scheduler")
        args.scheduler = "bucketed"
    prefix_on = args.prefix_cache == "on"
    if prefix_on and (args.cache_impl != "paged"
                      or not Engine.prefix_cache_supported(cfg)):
        print("# prefix caching needs a paged pure-GQA cache; ignoring "
              "--prefix-cache on")
        prefix_on = False
    plens = [int(s) for s in str(args.prompt_len).split(",") if s]
    max_seq = (max(plens) + args.shared_prefix + args.gen
               + (cfg.n_img_tokens if cfg.family == "vlm" else 0))
    mesh = None
    if args.mesh is not None:
        from .mesh import make_production_mesh, parse_mesh_arg

        mesh = make_production_mesh(shape=parse_mesh_arg(args.mesh))
    eng = Engine(
        cfg, slots=args.slots, max_seq=max_seq,
        cache_impl=args.cache_impl, page_size=args.page_size,
        num_pages=args.pages or None, rng_seed=args.seed,
        prefix_cache=prefix_on,
        fused_decode=args.fused_decode == "on",
        telemetry=Telemetry(profile=args.profile_spans),
        mesh=mesh,
    )
    rng = np.random.default_rng(args.seed)
    shared = (rng.integers(0, cfg.vocab, size=args.shared_prefix)
              if args.shared_prefix > 0 else None)
    queue = []
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab, size=plens[i % len(plens)])
        queue.append(tail if shared is None
                     else np.concatenate([shared, tail]))
    arrivals = None
    if args.arrival_rate > 0:
        inter = rng.exponential(1.0 / args.arrival_rate, size=args.requests)
        arrivals = np.floor(np.cumsum(inter)).astype(int)
    on_token = None
    if args.stream:
        def on_token(rid, tok, step):
            print(f"  step{step:4d} req{rid}: {tok}")
    outputs, stats = run(eng, queue, gen=args.gen,
                         temperature=args.temperature, seed=args.seed,
                         scheduler=args.scheduler, arrivals=arrivals,
                         chunk=args.chunk, on_token=on_token,
                         deadline_steps=args.deadline_steps or None,
                         max_tokens=args.max_tokens or None,
                         max_queue=args.max_queue or None,
                         watermark_high=args.watermark_high,
                         watermark_low=args.watermark_low)
    for rid in sorted(outputs):
        print(f"  req{rid}: {outputs[rid][:10]}...")
    for rid, (state, reason) in sorted(stats.get("statuses", {}).items()):
        if state != "finished":
            print(f"  req{rid}: {state} ({reason})")
    tel = stats.get("telemetry", eng.tel)
    if args.metrics_out:
        # the engine's registry plus the process-global one (autotune
        # gauges fire under jit tracing, before any Engine exists)
        from ..serving.telemetry import _atomic_write

        text = tel.to_prometheus()
        extra = default_registry().to_prometheus()
        if extra and default_registry() is not tel:
            text += extra
        _atomic_write(args.metrics_out, text)
        print(f"# metrics -> {args.metrics_out}")
    if args.trace_out:
        tel.write_chrome_trace(args.trace_out)
        print(f"# trace -> {args.trace_out}")
    return outputs


if __name__ == "__main__":
    main()
