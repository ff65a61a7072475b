"""Single-server JAX serving engine: slot-based continuous batching with
heterogeneous LoRA adapters applied through the batched bank (the real
compute path — co-batched requests genuinely pay the bank's max rank, so
the paper's interference is physically measurable here, not just modeled).

Prefill admission is batched: queued prompts of the SAME length are
packed into one prefill call (exact length — no padding pollution for
SSM state) and their cache rows scattered into slots in one fused merge.
Decode runs one jitted step for the whole slot batch; ``decode_steps(k)``
fuses k of them into a single host dispatch (``jax.lax.scan`` over the
decode step with on-device argmax and per-slot remaining-token
bookkeeping, cache donated through the scan), so decode costs one host
round-trip per k tokens instead of per token. Each slot row carries its
own cache position; a free slot's position is parked past the cache, so
its writes drop (out-of-bounds scatter semantics) and the decode
attention reads none of its blocks.

The engine is *placement-aware*: its bank holds only the adapters the
orchestrator placed (or fetched) onto this server, padded to that
subset's max rank — not the global one. ``load_adapters`` /
``evict_adapter`` rebuild the bank mid-flight, remapping the adapter
indices of co-batched slots, so a cluster rebalance can reshape a
server's bank while requests are decoding.

``bank_mode`` selects the bank layout (``repro.lora.bank.LoRABank``):
``"padded"`` (default, max-rank padding — the paper-faithful baseline)
or ``"bucketed"`` (power-of-two rank buckets, each at its own rank).
Both produce token-identical outputs; they differ only in compute cost,
which makes padded-vs-bucketed A/Bs meaningful on this real engine.

``mesh`` (a ("data", "model") Mesh, e.g. ``launch.mesh.make_engine_mesh``)
turns on the mesh-sharded serving mode: base weights, activations, and
the KV cache shard over the mesh, LoRA banks co-shard along
d_model/d_out so the SGMV kernels run per-shard with a single rank-r
psum (``serving.sharding``), and every jitted call traces under the
mesh + axis env. Token streams are identical to the single-device
engine — sharding changes placement and collectives, not numerics
(argmax decoding absorbs the psum reassociation rounding).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.lora.adapter import Adapter
from repro.lora.bank import build_bank
from repro.models import model as M

from .metrics import MetricsCollector
from .paging import UnifiedPagePool
from .request import Phase, ServeRequest

Request = ServeRequest


class ServingEngine:
    def __init__(self, cfg, params, adapter_ranks: Dict[str, int],
                 *, max_batch: int = 8, max_len: int = 512,
                 seed: int = 0, scaling: float = 1.0,
                 bank_mode: str = "padded", decode_block: int = 1,
                 lora_kernel: str = "einsum", mesh=None,
                 page_pool: Optional[UnifiedPagePool] = None,
                 clock: Callable[[], float] = time.monotonic,
                 tracer=None, server_id: int = 0):
        from .sharding import make_engine_sharding
        self.cfg = cfg
        # obs.Tracer: per-iteration spans (prefill groups, decode
        # dispatches) stamped on the engine clock, carrying the batch
        # shape so the drift meter can price them with ServerModel, and
        # their ``step`` children (where the host dispatches, waits on
        # the device, merges, and hands out tokens); with no tracer the
        # engine reads its clock no more often than it stamps requests
        self.tracer = tracer
        self._track = f"server:{server_id}"
        self.bank_mode = bank_mode
        self.decode_block = decode_block
        self.lora_kernel = lora_kernel
        self.page_pool = page_pool
        # mesh-sharded mode: a ("data","model") Mesh shards base
        # weights, KV cache, activations, and (co-sharded) LoRA banks;
        # None keeps the legacy single-device engine byte-for-byte
        self.sharding = make_engine_sharding(mesh, cfg, max_batch)
        if self.sharding is not None:
            params = self.sharding.shard_params(params)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self._clock = clock
        self._bank_key = jax.random.PRNGKey(seed)
        self.slots: List[Optional[ServeRequest]] = [None] * max_batch
        self.slot_adapter = jnp.zeros((max_batch,), jnp.int32)
        self.last_token = jnp.zeros((max_batch,), jnp.int32)
        self.metrics = MetricsCollector()
        self.queue: List[ServeRequest] = []
        self.completed: List[ServeRequest] = []
        self._iter = 0
        self.bank_rebuilds = 0
        # host-dispatch telemetry (bench_kernels: dispatches per token)
        self.decode_dispatches = 0
        self.prefill_dispatches = 0
        self.tokens_decoded = 0

        self.adapter_ranks: Dict[str, int] = {}
        self._rebuild_bank(dict(adapter_ranks))
        self.bank_rebuilds = 0          # the initial build doesn't count

        enc_len = (cfg.encoder.n_frames if cfg.encoder
                   else (cfg.n_frontend_tokens or None))
        self.cache = M.init_cache(cfg, max_batch, max_len,
                                  jnp.float32, enc_len=enc_len)
        if self.sharding is not None:
            self.cache = self.sharding.shard_cache(self.cache)

        cfgc = cfg
        kern = lora_kernel
        # MLA decodes through the absorbed path: the latent cache is read
        # once and never expanded per head (prefill expands it)
        absorbed = cfg.mla is not None

        def _decode(params, cache, tokens, bank, idx):
            return M.decode_step(cfgc, params, cache, tokens, bank=bank,
                                 lora_idx=idx, lora_kernel=kern,
                                 mla_absorbed=absorbed)

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._decode_k_cache = {}

        def _merge_many(cache, cache1, slots, pos):
            # scatter n freshly-prefilled rows (batch axis 1 everywhere
            # but "pos") into their slots in one fused update
            out = {}
            for k, v in cache.items():
                if k == "pos":
                    out[k] = v.at[slots].set(pos)
                elif k == M.COUNTERS:           # the engine's, not a row's
                    out[k] = v
                else:
                    out[k] = v.at[:, slots].set(cache1[k].astype(v.dtype))
            return out

        self._merge_many = jax.jit(_merge_many, donate_argnums=(0,))

        def _park(cache, free):
            return {**cache, "pos": jnp.where(free, max_len, cache["pos"])}

        self._park = jax.jit(_park, donate_argnums=(0,))
        # slots whose position is parked (every slot, from the start)
        self._parked = set(range(max_batch))
        self._prefill_cache = {}
        with self._ctx():
            self.cache = self._park(self.cache, np.ones(max_batch, bool))
            self._weight_stream = M.streams_weights(cfg, params)
            self._kv_attention = "kernel" if M.kernel_attention(
                cfg, cfg.sliding_window) else "xla"
        # the dispatch spans of a MoE engine name how many experts it holds
        self._experts_attr = {} if cfg.moe is None else {
            "experts_held": (cfg.moe.n_held if cfg.moe.holds_share
                             else cfg.moe.n_experts)}

    def expert_counters(self) -> Optional[dict]:
        """What the decode steps' held-expert layers routed, summed over
        every decode step so far (host ints; None without held experts):
        ``steps``, and per MoE layer ``pairs`` (routed pairs that landed
        on a held expert) and ``touched`` (held experts with at least
        one). Rows of free slots route too. Reading them syncs."""
        counts = self.cache.get(M.COUNTERS)
        if counts is None:
            return None
        return {k: np.asarray(v).tolist() for k, v in counts.items()}

    @property
    def weight_stream(self) -> bool:
        """Whether the decode program streams the block weights through
        ``kernels.stream`` (fp32 dense weights on an unsharded TPU
        engine) rather than leaving the dots to XLA."""
        return self._weight_stream

    @property
    def kv_attention(self) -> str:
        """``"kernel"`` where the decode program attends through
        ``kernels.decode_attention`` (GQA without a window on an
        unsharded TPU engine: each layer's live KV blocks read in place),
        else ``"xla"``."""
        return self._kv_attention

    def _ctx(self):
        """Mesh + axis-env context every jitted call runs under (tracing
        picks up the sharding constraints); a no-op when unsharded."""
        import contextlib
        if self.sharding is None:
            return contextlib.nullcontext()
        return self.sharding.ctx()

    def _span(self, name: str, start: float, end: float,
              attrs: Optional[dict] = None) -> None:
        """A ``step`` span on this server's track (tracer attached)."""
        self.tracer.record(name, start, end, cat="step", track=self._track,
                           attrs=attrs)

    # -- placement-aware bank management --------------------------------
    def _rebuild_bank(self, adapter_ranks: Dict[str, int]) -> None:
        t0 = self._clock() if self.tracer is not None else 0.0
        self.adapter_ranks = adapter_ranks
        n_layers = 1 if self.cfg.family == "hybrid" else self.cfg.n_layers
        self.lora_bank = build_bank(self.cfg, adapter_ranks, self._bank_key,
                                    mode=self.bank_mode, n_layers=n_layers)
        if self.sharding is not None:
            # re-apply the co-sharded layout on every rebuild: placement
            # changes (install/evict/rebalance) reshape the bank but must
            # not silently de-shard it
            import dataclasses
            self.lora_bank = dataclasses.replace(
                self.lora_bank,
                data=self.sharding.shard_bank(self.lora_bank.data))
        self.adapter_ids = list(self.lora_bank.adapter_ids)
        # O(1) id -> bank-row lookups on the admit path (rebuilt here, the
        # only place the layout changes)
        self._adapter_idx = {aid: i
                             for i, aid in enumerate(self.adapter_ids)}
        self.ranks = list(self.lora_bank.ranks)
        self.max_rank = self.lora_bank.max_rank  # padding = subset max
        self.bank = self.lora_bank.data
        self.bank_rebuilds += 1
        # remap adapter indices of co-batched slots to the new bank layout
        idx = [self._adapter_idx[r.adapter_id] if r is not None else 0
               for r in self.slots]
        self.slot_adapter = jnp.asarray(idx, jnp.int32)
        self._slot_lora = self.lora_bank.lora_idx(self.slot_adapter)
        if self.tracer is not None:
            self._span("bank.rebuild", t0, self._clock(), {
                "n_adapters": len(self.adapter_ids),
                "max_rank": self.max_rank,
                "bytes": sum(a.nbytes for a in jax.tree.leaves(self.bank))})

    def load_adapters(self, adapter_ranks: Dict[str, int]) -> bool:
        """Add adapters to this server's bank (placement update or pool
        fetch). Returns True if the bank was rebuilt."""
        new = {aid: r for aid, r in adapter_ranks.items()
               if aid not in self.adapter_ranks}
        if not new:
            return False
        self._rebuild_bank({**self.adapter_ranks, **new})
        return True

    # -- GDR remote-read data plane --------------------------------------
    def adapter_weights(self, adapter_id: str):
        """Serve one adapter's unpadded weights to a peer (what a GDR
        remote read against this server's bank returns)."""
        return self.lora_bank.get_adapter(adapter_id)

    def install_adapter(self, adapter_id: str, rank: int,
                        weights=None) -> bool:
        """Make ``adapter_id`` servable using weights read from a peer's
        bank instead of (re)materializing them locally: the bank is
        reshaped to make room, then the adapter's rows are overwritten
        with the peer bytes. With ``weights=None`` this degrades to a
        plain ``load_adapters`` (local materialization). Returns True if
        the bank was rebuilt."""
        added = self.load_adapters({adapter_id: rank})
        if weights is not None:
            self.lora_bank = self.lora_bank.set_adapter(adapter_id,
                                                        weights)
            if self.sharding is not None:
                # scatter of the peer rows de-constrains the layout;
                # re-pin the co-sharded placement
                import dataclasses
                self.lora_bank = dataclasses.replace(
                    self.lora_bank,
                    data=self.sharding.shard_bank(self.lora_bank.data))
            self.bank = self.lora_bank.data
        return added

    def evict_adapter(self, adapter_id: str) -> bool:
        """Drop an adapter from the bank. Refuses (returns False) while
        the adapter still has queued or co-batched requests, or if it is
        the server's last adapter."""
        if adapter_id not in self.adapter_ranks:
            return False
        if len(self.adapter_ranks) == 1:
            return False
        if any(r is not None and r.adapter_id == adapter_id
               for r in self.slots):
            return False
        if any(q.adapter_id == adapter_id for q in self.queue):
            return False
        self._rebuild_bank({aid: r for aid, r in self.adapter_ranks.items()
                            if aid != adapter_id})
        return True

    # ------------------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        if req.adapter_id not in self.adapter_ranks:
            raise KeyError(f"adapter {req.adapter_id!r} is not loaded on "
                           f"this server (hosted: {self.adapter_ids})")
        self.queue.append(req)

    def _adapter_index(self, adapter_id: str) -> int:
        return self._adapter_idx[adapter_id]

    def _prefill_fn(self, length: int):
        # keyed by (prompt length, bank layout signature): bank reshapes
        # after a rebalance retrigger tracing for that shape only; the
        # bucketed signature is the tuple of (bucket rank, count) pairs
        key = (length,) + self.lora_bank.signature
        if key not in self._prefill_cache:
            cfg = self.cfg
            kern = self.lora_kernel

            def _prefill(params, tokens, bank, idx, frontend=None):
                return M.prefill(cfg, params, tokens, frontend=frontend,
                                 bank=bank, lora_idx=idx,
                                 cache_len=self.max_len,
                                 cache_dtype=jnp.float32,
                                 lora_kernel=kern)

            self._prefill_cache[key] = jax.jit(_prefill)
        return self._prefill_cache[key]

    def _admit(self, now: float) -> None:
        free = [s for s in range(self.max_batch) if self.slots[s] is None]
        if not free or not self.queue:
            return
        take = self.queue[:len(free)]
        del self.queue[:len(take)]
        # batched prefill admission: FIFO-assign slots, then pack the
        # admitted prompts into same-length groups — one prefill call
        # per group (B = group size, exact length: no padding pollution
        # for SSM state) instead of B=1 each
        groups: Dict[int, list] = {}
        for req in take:
            slot = free.pop(0)
            groups.setdefault(len(req.prompt), []).append((slot, req))
        for length, grp in groups.items():
            self._prefill_group(length, grp)
        # slot -> (bucket, local) bank indices recomputed ONCE per admit
        # pass, not once per admitted slot
        self._slot_lora = self.lora_bank.lora_idx(self.slot_adapter)
        if self.tracer is not None:
            self._span("admit", now, self._clock(),
                       {"admitted": len(take), "groups": len(groups)})

    def _batch_shape_attrs(self, reqs, value) -> dict:
        """Span attrs describing a batch's rank shape: ``max_rank`` plus,
        in bucketed mode, per-rank-bucket sums of ``value(req)`` — the
        exact inputs the bucketed cost-model methods take."""
        from repro.lora.bank import rank_bucket
        ranks = [self.adapter_ranks[r.adapter_id] for r in reqs]
        attrs = {"max_rank": max(ranks), "bank_mode": self.bank_mode}
        if self.bank_mode == "bucketed":
            buckets: Dict[int, int] = {}
            for r, req in zip(ranks, reqs):
                b = rank_bucket(max(1, r))
                buckets[b] = buckets.get(b, 0) + value(req)
            attrs["buckets"] = buckets
        return attrs

    def _prefill_group(self, length: int, grp) -> None:
        t0 = self._clock()
        n = len(grp)
        aidx = []
        for slot, req in grp:
            ai = self._adapter_idx[req.adapter_id]
            aidx.append(ai)
            if self.page_pool is not None:
                # unified paging: KV pages for the sequence + the
                # adapter's pages (paged in on first use, pinned while
                # co-batched)
                self.page_pool.alloc_kv(f"req{req.req_id}", length)
                # footprint from the same formula the cluster/placement
                # accounting uses, not an ad-hoc per-target guess; hybrid
                # banks hold a single shared-attn LoRA layer, so the
                # per-layer share is what this server actually pages in
                nbytes = Adapter(req.adapter_id,
                                 self.ranks[ai]).nbytes(self.cfg)
                if self.cfg.family == "hybrid":
                    nbytes = max(1, nbytes // self.cfg.n_layers)
                self.page_pool.ensure_adapter(req.adapter_id, nbytes)
                self.page_pool.pin_adapter(req.adapter_id)
        toks = jnp.asarray([req.prompt for _, req in grp], jnp.int32)
        frontend = None
        if self.cfg.family == "vlm":
            frontend = jnp.zeros(
                (n, self.cfg.n_frontend_tokens, self.cfg.d_model))
        if self.cfg.family == "audio":
            frontend = jnp.zeros(
                (n, self.cfg.encoder.n_frames, self.cfg.d_model))
        n_programs = len(self._prefill_cache)
        fn = self._prefill_fn(length)
        lidx = self.lora_bank.lora_idx(jnp.asarray(aidx, jnp.int32))
        with self._ctx():
            if frontend is not None:
                logits, cache1 = fn(self.params, toks, self.bank, lidx,
                                    frontend)
            else:
                logits, cache1 = fn(self.params, toks, self.bank, lidx)
        self.prefill_dispatches += 1
        if self.tracer is not None:
            t_sync = self._clock()
            self._span("prefill.dispatch", t0, t_sync, {
                "new_program": len(self._prefill_cache) > n_programs,
                **self._experts_attr})
        firsts = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        if self.tracer is not None:
            t_merge = self._clock()
            self._span("prefill.sync", t_sync, t_merge)
        slots = jnp.asarray([slot for slot, _ in grp], jnp.int32)
        with self._ctx():
            self.cache = self._merge_many(self.cache, cache1, slots,
                                          jnp.full((n,), length,
                                                   jnp.int32))
        self._parked.difference_update(slot for slot, _ in grp)
        self.slot_adapter = self.slot_adapter.at[slots].set(
            jnp.asarray(aidx, jnp.int32))
        self.last_token = self.last_token.at[slots].set(
            jnp.asarray(firsts))
        t = self._clock()
        for i, (slot, req) in enumerate(grp):
            req.phase = Phase.DECODE
            req.slot = slot
            req.output.append(int(firsts[i]))
            req.t_first_token = t
            req.prefill_start = t0
            req.prefill_done = t
            self.slots[slot] = req
        if self.tracer is not None:
            self._span("prefill.merge", t_merge, t)
            reqs = [req for _, req in grp]
            attrs = self._batch_shape_attrs(reqs, lambda r: length)
            attrs.update(tokens=n * length, batch=n)
            self.tracer.record("prefill", t0, t, cat="iteration",
                               track=self._track, attrs=attrs)

    def _finish_token(self, slot: int, req: ServeRequest, token: int,
                      now: float) -> None:
        """Record one decoded token for a slot; free the slot if done."""
        req.output.append(token)
        self.tokens_decoded += 1
        if self.page_pool is not None:
            self.page_pool.grow_kv(f"req{req.req_id}",
                                   len(req.prompt) + len(req.output))
        done = len(req.output) >= req.max_new_tokens
        if done or len(req.prompt) + len(req.output) >= self.max_len:
            req.phase = Phase.DONE
            req.t_finish = now
            req.finish = now
            self.metrics.record(req)
            self.completed.append(req)
            self.slots[slot] = None
            if self.page_pool is not None:
                self.page_pool.free_kv(f"req{req.req_id}")
                if not any(r is not None and
                           r.adapter_id == req.adapter_id
                           for r in self.slots):
                    self.page_pool.pin_adapter(req.adapter_id, False)

    def _park_free(self) -> None:
        """Park the positions of the slots freed since the last decode
        past the cache (one small program for any set of slots)."""
        free = [s for s, r in enumerate(self.slots)
                if r is None and s not in self._parked]
        if free:
            mask = np.zeros(self.max_batch, bool)
            mask[free] = True
            with self._ctx():
                self.cache = self._park(self.cache, mask)
            self._parked.update(free)

    def _decode_once(self) -> None:
        if not any(s is not None for s in self.slots):
            return
        self._park_free()
        t0 = self._clock()
        active = [r for r in self.slots if r is not None]
        with self._ctx():
            logits, self.cache = self._decode(
                self.params, self.cache, self.last_token, self.bank,
                self._slot_lora)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.last_token = nxt
        self.decode_dispatches += 1
        if self.tracer is not None:
            t_sync = self._clock()
        # analysis: ignore[host-sync] the iteration's single sync point
        nxt_np = np.asarray(nxt)
        now = self._clock()
        if self.tracer is not None:
            self._decode_spans(active, t0, t_sync, now, 1)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            self._finish_token(slot, req, int(nxt_np[slot]), now)
        self._iter += 1
        if self.tracer is not None:
            self._span("decode.tokens", now, self._clock())

    def _decode_spans(self, active, t0: float, t_sync: float, now: float,
                      k: int) -> None:
        """The ``decode`` iteration span of one dispatch of ``k`` fused
        steps, and its ``decode.dispatch`` / ``decode.sync`` children.
        ``kv_live``: the live tokens of the dispatch's first step (those
        its attention reads) over ``max_batch * max_len``."""
        live = sum(min(len(r.prompt) + len(r.output), self.max_len)
                   for r in active)
        self._span("decode.dispatch", t0, t_sync, {
            "weights": "stream" if self._weight_stream else "xla",
            "attention": self._kv_attention,
            "kv_live": live / (self.max_batch * self.max_len),
            **self._experts_attr})
        self._span("decode.sync", t_sync, now)
        attrs = self._batch_shape_attrs(active, lambda r: 1)
        attrs.update(batch=len(active), steps=k, iters=k)
        self.tracer.record("decode", t0, now, cat="iteration",
                           track=self._track, attrs=attrs)

    # -- multi-token decode steps ---------------------------------------
    def _decode_k_fn(self, k: int):
        """jitted k-step fused decode, cached per k (and retraced per
        bank signature by jit itself)."""
        if k not in self._decode_k_cache:
            cfg = self.cfg
            kern = self.lora_kernel

            def _decode_k(params, cache, tokens, bank, idx, steps_left):
                def body(carry, _):
                    cache, tok, left = carry
                    logits, cache = M.decode_step(cfg, params, cache, tok,
                                                  bank=bank, lora_idx=idx,
                                                  lora_kernel=kern)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    active = left > 0
                    # rows past their budget freeze: their cache keeps
                    # advancing (writes are dropped on host) but the
                    # emitted token repeats and is discarded
                    nxt = jnp.where(active, nxt, tok)
                    return (cache, nxt, left - active.astype(left.dtype)), \
                        nxt

                (cache, tok, left), toks = jax.lax.scan(
                    body, (cache, tokens, steps_left), None, length=k)
                return cache, tok, toks

            self._decode_k_cache[k] = jax.jit(_decode_k,
                                              donate_argnums=(1,))
        return self._decode_k_cache[k]

    def decode_steps(self, k: int) -> int:
        """Run ``k`` decode iterations in ONE host dispatch: a
        ``lax.scan`` over the fused decode step with on-device argmax and
        per-slot remaining-token bookkeeping, cache donated through the
        scan. Returns the number of fused iterations run. Token streams
        are identical to ``k`` single ``step()`` calls; only admission
        granularity (every k tokens instead of every token) and finish-
        timestamp granularity are coarser."""
        if not any(s is not None for s in self.slots):
            return 0
        self._park_free()
        t0 = self._clock()
        left = [0] * self.max_batch
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            # mirror _decode_once: an active slot always decodes at
            # least one more token, then finishes on whichever budget
            # (max_new_tokens or max_len) it crosses first
            left[slot] = max(1, min(req.max_new_tokens - len(req.output),
                                    self.max_len - len(req.prompt)
                                    - len(req.output)))
        # always dispatch the full k-step scan (rows past their budget
        # freeze on device): one trace per (k, bank signature) instead
        # of retracing for every distinct tail length
        fn = self._decode_k_fn(k)
        with self._ctx():
            self.cache, self.last_token, toks = fn(
                self.params, self.cache, self.last_token, self.bank,
                self._slot_lora, jnp.asarray(left, jnp.int32))
        self.decode_dispatches += 1
        if self.tracer is not None:
            t_sync = self._clock()
        # analysis: ignore[host-sync] ONE sync per k tokens, by design
        toks_np = np.asarray(toks)
        now = self._clock()
        if self.tracer is not None:
            self._decode_spans([r for r in self.slots if r is not None],
                               t0, t_sync, now, k)
        for step in range(k):
            for slot, req in enumerate(self.slots):
                if req is None or step >= left[slot]:
                    continue
                self._finish_token(slot, req, int(toks_np[step, slot]),
                                   now)
        self._iter += k
        if self.tracer is not None:
            self._span("decode.tokens", now, self._clock())
        return k

    def step(self) -> None:
        """One engine iteration: admit then decode (prefill-prioritized).
        With ``decode_block > 1`` each step decodes up to that many
        tokens per slot in a single fused host dispatch."""
        t0 = self._clock()
        self._admit(t0)
        if self.decode_block > 1:
            self.decode_steps(self.decode_block)
        else:
            self._decode_once()
        if self.tracer is not None:
            self._span("engine.step", t0, self._clock())

    def drain_completed(self) -> List[ServeRequest]:
        done, self.completed = self.completed, []
        return done

    def cancel(self, req_id: int) -> Optional[ServeRequest]:
        """Abort a live request: drop it from the queue, or free its
        batch slot (and KV pages, and the adapter pin if it was the
        last co-batched user). Returns the request, or None if it is
        not live on this engine."""
        for r in self.queue:
            if r.req_id == req_id:
                self.queue = [q for q in self.queue if q is not r]
                return r
        for slot, r in enumerate(self.slots):
            if r is not None and r.req_id == req_id:
                self.slots[slot] = None
                if self.page_pool is not None:
                    self.page_pool.free_kv(f"req{r.req_id}")
                    if not any(q is not None
                               and q.adapter_id == r.adapter_id
                               for q in self.slots):
                        self.page_pool.pin_adapter(r.adapter_id, False)
                return r
        return None

    def run_until_drained(self, max_iters: int = 100_000) -> dict:
        it = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and it < max_iters:
            self.step()
            it += 1
        return self.metrics.summary()

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)
