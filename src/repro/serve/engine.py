"""Production serving engine: batched prefill + decode for every arch family.

Wraps the jitted ``prefill``/``serve_step`` callables (the same ones the
multi-pod dry-run compiles) behind a request-batch API.  On real hardware the
mesh is the production mesh; on CPU it serves reduced configs for tests and
examples.

The default (``fused=True``) path compiles the whole request into two
programs: one bucketed ``api.prefill_bucketed`` call that fills the KV cache
with the entire prompt, and one ``lax.scan``-fused decode loop that emits
every generated token in a single dispatch (DESIGN.md §1).  ``fused=False``
keeps the original one-dispatch-per-token reference loop for parity testing.

Every compiled shape is bucketed to a power of two (prompt width, decode
steps, batch), so the jit caches stay O(log max_len) no matter how ragged
the request mix is.  ``eos_id`` enables per-request stop tokens with exact
generated-length reporting.

For the continuous-batching scheduler (serve/scheduler.py) the engine also
exposes the slot protocol: ``init_slot_cache`` / ``prefill_slot`` /
``insert_slot`` / ``decode_slots`` — a fixed ``(max_slots, ...)`` cache
pytree where each slot is an independent request stream, admitted mid-flight
by a bucketed B=1 prefill and advanced by ONE persistent masked decode step.
Interface-traffic accounting (``meter``) replays eq. 7-10 bytes per *active*
token (DESIGN.md §4).

``page_size=N`` switches the slot cache to the paged layout (serve/pages.py,
DESIGN.md §5-6): sequence-growing cache leaves live in a shared page pool
with a host-owned per-slot page table, allocated on demand and freed on EOS,
so resident KV bytes track actual occupancy instead of max_slots × max_len.
The default paged decode step (``paged_attn="inplace"``) appends each active
slot's token to its page and computes attention DIRECTLY through the traced
table (``api.paged_decode_step`` -> ``ops.paged_decode_attention``), so no
dense-view transient exists and steady-state KV reads are O(live tokens)
per slot; ``paged_attn="gather"`` keeps the PR-3 reference discipline
(gather dense view -> same family decode math -> scatter one token) as the
fallback/oracle.  Either way: fixed shapes throughout, zero steady-state
recompiles.  Leaves that do not scale with ``max_len`` (recurrent state,
window ring buffers) pass through dense — the recurrent families' no-op
page table.  ``prefill_chunk_slot`` feeds a prompt as fixed-width chunks so
the scheduler can interleave prefill with decode (chunked prefill).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.splitbrain import TrafficMeter, TrafficModel
from repro.distributed import runtime
from repro.distributed import sharding as shd
from repro.kernels import ops
from repro.launch.mesh import make_test_mesh
from repro.models import api
from repro.serve import pages as pages_mod
from repro.serve.errors import InvalidRequestError
from repro.serve import slots as slots_mod
from repro.serve import telemetry
from repro.train import step as step_mod


def make_paged_step(cfg: ModelConfig, ba, sa, paged_attn: str = "inplace"):
    """The masked paged decode program ``decode_slots`` jits (its name,
    ``paged_step``, is what a profile calls it): ``(params, pool, table,
    tokens, active, corrupt) -> (next tokens, finite-logits flags, pool)``.

    ``"inplace"`` appends and attends through the page table with the pool
    updated in place (``api.paged_decode_step``); ``"gather"`` is the
    reference discipline: gather the dense view, run the family
    ``decode_step``, scatter the one new token per active slot back."""
    if paged_attn == "inplace":
        def paged_step(params, pcache, table, toks, act_m, bad):
            logits, pc = api.paged_decode_step(
                params, pcache, table, toks, cfg, write=act_m, seq_axes=sa)
            logits = slots_mod.corrupt_logits(logits, bad)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = slots_mod.finite_logits(logits)
            return nxt, ok, pc
    else:
        def paged_step(params, pcache, table, toks, act_m, bad):
            view = pages_mod.gather_tree(pcache, table, ba, sa,
                                         cfg.resolved_head_dim)
            pos = view["len"]
            logits, new = api.decode_step(params, view, toks, cfg)
            logits = slots_mod.corrupt_logits(logits, bad)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = slots_mod.finite_logits(logits)
            new = slots_mod.select_slots(act_m, new, view, ba)
            pc = pages_mod.scatter_token_tree(
                pcache, new, table, pos, act_m, ba, sa)
            return nxt, ok, pc
    return paged_step


class ServeEngine(pages_mod.PagedEngineMixin):
    def __init__(self, cfg: ModelConfig, params, mesh=None, max_len: int = 128,
                 fused: bool = True, page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 paged_attn: str = "inplace", prefix_cache: str = "off",
                 kv_dtype: str = "bf16"):
        # the default mesh is ONE device: tensor parallelism is asked for
        # with an explicit (1, tp) mesh, never inherited from the host
        self.mesh = mesh if mesh is not None else make_test_mesh(
            jax.devices()[:1])
        # Serve programs trace with exact_tp: every down-projection input is
        # gathered before its contraction (shd.pin_tp_exact), so the sharded
        # step is BITWISE identical to single-device greedy — the serve
        # token-identity contract (DESIGN.md §11).  No-op on a 1-device mesh.
        # The kernel set (kernels/ops.py) is chosen here, once, from the
        # mesh's platform: compiled Pallas on TPU, jnp oracles elsewhere.
        cfg = dataclasses.replace(
            cfg, parallel=dataclasses.replace(cfg.parallel, exact_tp=True),
            kernels=ops.for_platform(self.mesh.devices.flat[0].platform))
        self.cfg = cfg
        # tensor-parallel degree of the serving mesh (DESIGN.md §11): the
        # stacked params shard COLUMN-only (serve_param_pspecs — row cuts
        # would split contraction sums and break bf16 token identity) and
        # the slot KV state cuts on heads.  tp == 1 (the 1-device test
        # mesh) reproduces the single-device layout exactly.
        self._tp = (int(self.mesh.shape[cfg.parallel.model_axis])
                    if cfg.parallel.model_axis in self.mesh.axis_names else 1)
        self._param_sh = shd.with_sharding(
            self.mesh, shd.serve_param_pspecs(params, cfg, self.mesh))
        with runtime.use_mesh(self.mesh):
            self.params = jax.device_put(params, self._param_sh)
        self.max_len = max_len
        self.fused = fused
        self.meter = TrafficMeter()
        self._traffic = TrafficModel.for_config(cfg)
        # slot decode runs requests at ragged positions: the lockstep
        # scalar-index cache write (Perf H2) is wrong there, so the slot
        # programs compile against this variant of the config.
        self._ragged_cfg = dataclasses.replace(
            cfg, parallel=dataclasses.replace(cfg.parallel,
                                              aligned_decode=False))
        self._serve_step = None
        self._prefill_jit: Dict[int, Any] = {}         # keyed by bucket width
        self._loop_jit: Dict[Tuple[int, Optional[int]], Any] = {}
        self._slot_step_jit: Dict[int, Any] = {}       # keyed by n_slots
        self._slot_insert = None
        self._axes = None
        # ---- paged slot cache (page_size=None keeps the dense slot layout)
        self.page_size = page_size
        self.num_pages = num_pages
        self._pager = (pages_mod.HostPager(page_size, num_pages, max_len)
                       if page_size is not None else None)
        self._paged_attn = self.check_paged_attn(paged_attn)
        self._prefix_cache_on = self.check_prefix_cache(prefix_cache)
        # pool storage format (DESIGN.md §13): int8/fp8 pages quantize on
        # write and dequantize inside the decode kernel's page fetch
        self._kv_dtype = pages_mod.check_kv_dtype(kv_dtype, page_size)
        self._fq_jit = None                    # post-prefill fake-quant pass
        self._paging_active = False            # set by init_slot_cache
        self._seq_ax = None
        self._paged_step = None
        self._b1_shape = None                  # B=1 request-cache eval_shape
        self._chunk_jit: Dict[int, Any] = {}   # keyed by chunk width
        # the lm fused chunk path needs every cache slot linear (non-ring)
        self._chunk_block_ok = (
            cfg.family == "lm" and not cfg.cross_attn_every
            and all(s.window is None or s.window >= max_len
                    for s in cfg.layer_pattern))

    # -------------------------------------------------------- jitted programs
    def _get_serve_step(self, cache):
        if self._serve_step is None:
            self._serve_step = step_mod.make_serve_step(
                self.cfg, self.mesh, self.params, cache, donate=False,
                param_spec_fn=shd.serve_param_pspecs,
                cache_spec_fn=shd.serve_cache_pspecs)
        return self._serve_step

    def _get_prefill(self, cache, width: int):
        """Bucketed prefill program; ``width`` must be a power-of-two bucket.
        One entry per bucket -> O(log max_len) compiles total."""
        if width not in self._prefill_jit:
            self._prefill_jit[width] = step_mod.make_bucketed_prefill(
                self.cfg, self.mesh, self.params, cache,
                cache_spec_fn=shd.serve_cache_pspecs,
                param_spec_fn=shd.serve_param_pspecs)
        return self._prefill_jit[width]

    # ------------------------------------------------- TP serving placements
    def _cache_shardings(self, tree_like):
        """NamedSharding pytree for a dense cache under the serve rules
        (head-cut KV; identical to replicated on a 1-device mesh)."""
        return shd.with_sharding(
            self.mesh,
            shd.serve_cache_pspecs(tree_like, self._ragged_cfg, self.mesh))

    def _b1_shardings(self):
        if self._b1_sh is None:
            if self._b1_shape is None:
                self._b1_shape = jax.eval_shape(
                    lambda: api.init_cache(self.cfg, 1, self.max_len))
            self._b1_sh = self._cache_shardings(self._b1_shape)
        return self._b1_sh

    def _vec_shardings(self, n: int) -> NamedSharding:
        """Placement of a per-slot (n,) vector (tokens / active mask)."""
        ax = shd.MeshAxes(self.mesh, self.cfg)
        b = ax.resolve("batch")
        if b is None or n % ax.size(b) != 0:
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, P(b))

    def _get_decode_loop(self, cache, steps: int, eos_id: Optional[int]):
        key = (steps, eos_id)
        if key not in self._loop_jit:
            self._loop_jit[key] = step_mod.make_decode_loop(
                self.cfg, self.mesh, self.params, cache, steps, eos_id=eos_id,
                param_spec_fn=shd.serve_param_pspecs,
                cache_spec_fn=shd.serve_cache_pspecs)
        return self._loop_jit[key]

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compiled-program census (bench/test introspection)."""
        return {
            "prefill_buckets": len(self._prefill_jit),
            "loop_buckets": len(self._loop_jit),
            "slot_steps": len(self._slot_step_jit),
            "chunk_widths": len(self._chunk_jit),
        }

    # ----------------------------------------------------- traffic accounting
    @property
    def traffic_shards(self) -> int:
        """How many ways the boundary-traffic accounting splits per token.

        Equals the mesh's TP degree when every counted channel width
        (d_model, kv_dim, vocab) divides exactly — each shard then crosses
        ``full/tp`` bytes and the per-shard entries sum to the single-device
        analytical model TO THE BYTE (DESIGN.md §11).  Any indivisible
        width falls back to 1 (single aggregate entry), because an
        approximate split would break the exactness contract."""
        tp, tm = self._tp, self._traffic
        if (tp > 1 and tm.d_model % tp == 0 and tm.kv_dim % tp == 0
                and tm.vocab_size % tp == 0):
            return tp
        return 1

    @telemetry.traced("engine.meter")
    def meter_tokens(self, n: int) -> None:
        """Replay ``n`` active tokens' boundary crossings on the meter.

        Aggregate form of the split-brain per-token log (same names, same
        eq. 7-10 widths, bytes == n * TrafficModel.bytes_per_token()); the
        accounting rule for masked decode is that ONLY active slots cross
        the interface (DESIGN.md §4).  On a TP mesh the replay logs ONE
        entry per model shard at ``width/tp`` (``traffic_shards``): the
        host scatters each shard its input slice and collects its KV/logit
        slice, so boundary bytes do not duplicate across shards and the
        totals — hence every exactness assertion — are unchanged.
        """
        n = int(n)
        if n <= 0:
            return
        tm = self._traffic
        shards = self.traffic_shards
        for _ in range(shards):
            self.meter.h2d("x_qkv_in", (n, tm.num_layers,
                                        tm.d_model // shards))
            self.meter.d2h("kv_out", (n, tm.num_layers, 2,
                                      tm.kv_dim // shards))
            self.meter.h2d("attn_in", (n, tm.num_layers,
                                       tm.d_model // shards))
            self.meter.d2h("logits", (n, tm.vocab_size // shards))

    def measured_bytes(self, count_q: bool = False) -> Dict[str, int]:
        """Total metered boundary bytes (paper accounting: K/V + attention +
        logits; ``count_q=True`` adds the QKV input activations)."""
        return self.meter.measured_bytes(count_q)

    # --------------------------------------------------------------- generate
    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 frontend: Optional[jnp.ndarray] = None,
                 fused: Optional[bool] = None,
                 eos_id: Optional[int] = None) -> Dict[str, Any]:
        """Greedy-decode a batch. prompts: (B, T0) int32 (right-aligned).

        ``eos_id``: per-request stop token.  Output rows are padded with
        ``eos_id`` past each request's stop, and ``gen_len`` reports the
        exact generated length (EOS inclusive, capped at ``max_new``).
        """
        if fused is None:
            fused = self.fused
        cfg = self.cfg
        B, T0 = prompts.shape
        if T0 - 1 + max_new > self.max_len:
            raise ValueError(
                f"request does not fit the cache: prompt_len={T0} + "
                f"max_new={max_new} needs {T0 - 1 + max_new} positions but "
                f"max_len={self.max_len}")
        with runtime.use_mesh(self.mesh):
            if not fused:
                cache = api.init_cache(cfg, B, self.max_len, frontend=frontend,
                                       params=self.params)
                return self._generate_stepwise(cache, prompts, max_new, eos_id)
            # bucket the batch too: pad with copies of row 0, slice outputs
            Bb = slots_mod.bucket(B)
            prompts_j = jnp.asarray(prompts, jnp.int32)
            if Bb > B:
                prompts_j = jnp.concatenate(
                    [prompts_j, jnp.broadcast_to(prompts_j[:1],
                                                 (Bb - B, T0))], axis=0)
                if frontend is not None:
                    frontend = jnp.concatenate(
                        [frontend, jnp.broadcast_to(
                            frontend[:1], (Bb - B,) + frontend.shape[1:])],
                        axis=0)
            cache = api.init_cache(cfg, Bb, self.max_len, frontend=frontend,
                                   params=self.params)
            tok = prompts_j[:, -1]
            if T0 > 1:
                # one bucketed api.prefill_bucketed pass fills the cache with
                # the whole prompt (no T0 Python-loop decode steps)
                width = slots_mod.bucket(T0 - 1)
                body = prompts_j[:, :-1]
                if width > T0 - 1:
                    body = jnp.pad(body, ((0, 0), (0, width - (T0 - 1))))
                prefill = self._get_prefill(cache, width)
                _, cache = prefill(self.params, cache, body,
                                   np.int32(T0 - 1))
            # bucketed step count: run the bucket, slice to max_new (greedy
            # decode is prefix-stable, so the extra steps change nothing)
            steps = slots_mod.bucket(max_new)
            loop = self._get_decode_loop(cache, steps, eos_id)
            t0 = time.perf_counter()
            toks, _, cache, gen_len = loop(self.params, cache, tok)
            toks = jax.block_until_ready(toks)
            dt = time.perf_counter() - t0
        toks = np.asarray(toks)[:B, :max_new]
        gen_len = np.minimum(np.asarray(gen_len)[:B], max_new)
        self.meter_tokens(B * (T0 - 1) + int(gen_len.sum()))
        return {"tokens": toks,
                "gen_len": gen_len,
                "tokens_per_s": int(gen_len.sum()) / dt,
                "decode_s": dt}

    def _generate_stepwise(self, cache, prompts: np.ndarray, max_new: int,
                           eos_id: Optional[int] = None):
        """Reference loop: one jitted dispatch per token (prefill included).

        EOS semantics mirror the fused loop exactly: finished rows keep
        stepping in lockstep but emit (and are fed) ``eos_id``; the loop may
        break early once every row has stopped, padding the remainder.
        """
        step = self._get_serve_step(cache)
        B = prompts.shape[0]
        tok = jnp.asarray(prompts[:, 0], jnp.int32)
        for t in range(1, prompts.shape[1]):
            _, _, cache = step(self.params, cache, tok)
            tok = jnp.asarray(prompts[:, t], jnp.int32)
        out = []
        alive = np.ones((B,), bool)
        gen_len = np.zeros((B,), np.int32)
        t0 = time.perf_counter()
        for _ in range(max_new):
            tok, logits, cache = step(self.params, cache, tok)
            emitted = np.asarray(tok)
            gen_len += alive
            if eos_id is not None:
                emitted = np.where(alive, emitted, eos_id)
                alive &= emitted != eos_id
                tok = jnp.asarray(emitted, jnp.int32)
            out.append(emitted)
            if eos_id is not None and not alive.any():
                break
        dt = time.perf_counter() - t0
        while len(out) < max_new:
            out.append(np.full((B,), eos_id, np.int32))
        tokens = np.stack(out, axis=1)
        self.meter_tokens(B * (prompts.shape[1] - 1) + int(gen_len.sum()))
        return {"tokens": tokens,
                "gen_len": gen_len,
                "tokens_per_s": int(gen_len.sum()) / dt,
                "decode_s": dt}

    # ---------------------------------------------------------- slot protocol
    # Consumed by serve/scheduler.py: a fixed (max_slots, ...) cache pytree
    # where every slot is an independent request stream.
    def _slot_axes(self):
        if self._axes is None:
            a = jax.eval_shape(lambda: api.init_cache(self.cfg, 1, self.max_len))
            b = jax.eval_shape(lambda: api.init_cache(self.cfg, 2, self.max_len))
            self._axes = slots_mod.batch_axes(a, b)
        return self._axes

    def _slot_seq_axes(self):
        """Per-leaf sequence axis (-1 = does not page), by shape diffing two
        ``max_len`` builds — mirrors the batch-axis discovery above.  Dense
        engines discover with an arbitrary delta (the answer is delta-free
        for any delta no window equals); the result also feeds the KV-read
        byte accounting, which applies to every layout."""
        if self._seq_ax is None:
            ps = self.page_size or 8
            a = jax.eval_shape(lambda: api.init_cache(self.cfg, 2, self.max_len))
            b = jax.eval_shape(
                lambda: api.init_cache(self.cfg, 2, self.max_len + ps))
            self._seq_ax = pages_mod.seq_axes(a, b, ps)
        return self._seq_ax

    def init_slot_cache(self, n_slots: int):
        """Fixed-shape batched cache, one slot per concurrent stream.

        With ``page_size`` set, sequence-growing leaves are allocated as a
        shared page pool instead (serve/pages.py) and a fresh host-side
        :class:`~repro.serve.pages.PagePool` tracks the per-slot page
        tables; everything else keeps the dense ``(n_slots, ...)`` layout.
        """
        if self.cfg.frontend_tokens or self.cfg.cross_attn_every:
            raise ValueError(
                "continuous batching covers the text-only families "
                "(frontend_tokens / cross-attention configs are not "
                "slot-servable)")
        shape = jax.eval_shape(
            lambda: api.init_cache(self.cfg, n_slots, self.max_len))
        ba, sa = self._slot_axes(), self._slot_seq_axes()
        self._note_slot_cache(n_slots, shape, ba, sa)
        if not self.will_page():
            if self._kv_dtype != "bf16":
                raise ValueError(
                    f"kv_dtype={self._kv_dtype!r} requires a paging family: "
                    f"no cache leaf of this config scales with max_len, so "
                    f"there is no page pool to quantize")
            # recurrent/ring-only families have nothing that scales with
            # max_len: the page table is a no-op and the dense layout IS
            # the occupancy-proportional one — skip pool bookkeeping.
            self._paging_active = False
            with runtime.use_mesh(self.mesh):
                cache = api.init_cache(self.cfg, n_slots, self.max_len)
                return jax.device_put(cache, self._cache_shardings(shape))
        if (self._paged_attn == "inplace"
                and self.cfg.parallel.decode_attn == "shard_map"):
            # ops.paged_decode_attention has no seq-sharded (dist_axis)
            # variant: refuse when paging actually engages rather than
            # silently dropping the sharding the config asked for
            # (DESIGN.md §6); never-paging families take the dense
            # fallback above and keep working.
            raise ValueError(
                "paged_attn='inplace' does not support "
                "parallel.decode_attn='shard_map' (the page pool is not "
                "sequence-sharded); serve this config with "
                "paged_attn='gather' or the dense slot cache")
        self._paging_active = True
        pool = self._pager.reset(n_slots)
        # head-cut pool placement (DESIGN.md §11): each model shard owns
        # Hkv/tp kv heads, (num_pages, ps, Hkv/(f*tp), f*hd) of the folded
        # pool (the fold keeps that cut wherever tp divides Hkv); the rules
        # auto-replicate any leaf whose head rows the TP degree does not
        # divide (the Hkv < tp fallback), in which case the per-shard byte
        # accounting stays 1-way
        pshape = pages_mod.pool_shape(shape, ba, sa, pool.num_pages,
                                      self.page_size, self._kv_dtype,
                                      self._tp)
        pool_specs = shd.pool_pspecs(pshape, self._ragged_cfg, self.mesh, sa)
        self._pool_sh = shd.with_sharding(self.mesh, pool_specs)
        self._b1_sh = None
        self._b1_shardings()
        self._note_slot_cache(n_slots, shape, ba, sa,
                              self._kv_cut(pool_specs, sa))
        self._kv_quant_tok_bytes = (
            pages_mod.kv_token_bytes_quant(shape, ba, sa, self.page_size,
                                           self._kv_dtype)
            if self._kv_dtype != "bf16" else None)
        self._pager.prefix_on = self.prefix_sharing_active()
        with runtime.use_mesh(self.mesh):
            return pages_mod.make_pool(shape, ba, sa, pool.num_pages,
                                       self.page_size,
                                       shardings=self._pool_sh,
                                       kv_dtype=self._kv_dtype, tp=self._tp)

    def _kv_cut(self, pool_specs, sa) -> int:
        return shd.pool_kv_cut(pool_specs, sa, self._tp,
                               self.cfg.parallel.model_axis)

    # reserve_slot / can_ever_admit / free_slot / cache_stats come from
    # pages_mod.PagedEngineMixin (dense engines admit everything, no-ops).
    def _stats_seq_axes(self):
        return self._slot_seq_axes()

    def prefill_slot(self, prompt: np.ndarray):
        """Prefill ONE request into a fresh B=1 cache (bucketed width).

        prompt (T0,) -> (single-request cache, input token for the next
        decode step).  The returned cache is slot-shaped: insert_slot writes
        it into the batched cache without reshaping.
        """
        prompt = np.asarray(prompt, np.int32)
        T0 = prompt.shape[0]
        if T0 < 1:
            raise InvalidRequestError(
                "prefill_slot needs a non-empty prompt (the last token "
                "seeds decoding)")
        with runtime.use_mesh(self.mesh):
            cache = api.init_cache(self.cfg, 1, self.max_len)
            if T0 > 1:
                width = slots_mod.bucket(T0 - 1)
                body = np.zeros((1, width), np.int32)
                body[0, :T0 - 1] = prompt[:-1]
                prefill = self._get_prefill(cache, width)
                _, cache = prefill(self.params, cache, jnp.asarray(body),
                                   np.int32(T0 - 1))
                if self._kv_dtype != "bf16":
                    cache = self._fake_quant_b1(cache)
        return cache, int(prompt[-1])

    def _fake_quant_b1(self, cache):
        """Round-trip the completed pages of a B=1 request cache through the
        page quantizer (pages_mod.fake_quant_tree): dense prefill values
        become exactly the values pool insertion will store, so the decode
        tokens that follow match the quantized pool bit-for-bit — the knob's
        token-identity story for prefix on/off (DESIGN.md §13)."""
        if self._fq_jit is None:
            sa = self._slot_seq_axes()
            ps, kvd = self.page_size, self._kv_dtype

            def fq(cache):
                return pages_mod.fake_quant_tree(cache, cache["len"][0], sa,
                                                 ps, kvd)

            b1_sh = self._b1_shardings()
            self._fq_jit = jax.jit(fq, donate_argnums=(0,),
                                   in_shardings=(b1_sh,), out_shardings=b1_sh)
        return self._fq_jit(cache)

    def new_request_cache(self):
        """Fresh B=1 cache for chunked prefill (slot-shaped, empty)."""
        with runtime.use_mesh(self.mesh):
            cache = api.init_cache(self.cfg, 1, self.max_len)
            return jax.device_put(cache, self._b1_shardings())

    @telemetry.traced("engine.seed_request_cache")
    def seed_request_cache(self, cache, slot: int, cached_len: int):
        """Prefix-aware prefill entry: B=1 request cache seeded with the
        slot's matched prefix pages gathered from the pool, ``len`` set to
        ``cached_len`` — the tail chunk stream continues from there."""
        if self._b1_shape is None:
            self._b1_shape = jax.eval_shape(
                lambda: api.init_cache(self.cfg, 1, self.max_len))
        with runtime.use_mesh(self.mesh):
            return self.paged_seed(cache, slot, cached_len,
                                   self._slot_axes(), self._slot_seq_axes(),
                                   self._b1_shape)

    @telemetry.traced("engine.prefill_chunk_slot")
    def prefill_chunk_slot(self, cache, chunk: np.ndarray, true_w: int):
        """Advance a B=1 request cache by one right-padded prompt chunk.

        chunk (W,) with W the FIXED chunk width (one compiled program per
        width, donated cache); only the first ``true_w`` tokens are real.
        The scheduler interleaves these with batched decode steps so a long
        prompt never head-of-line-blocks the decoding slots (DESIGN.md §5).
        """
        chunk = np.asarray(chunk, np.int32)
        W = chunk.shape[0]
        pages_mod.check_chunk_width(W, self.max_len)
        if W not in self._chunk_jit:
            block = self._chunk_block_ok
            sa = self._slot_seq_axes()
            ps, kvd = self.page_size, self._kv_dtype

            def chunk_fn(params, cache, tokens, true_len):
                cache = api.prefill_chunk(params, cache, tokens, true_len,
                                          self.cfg, block=block)
                if kvd != "bf16":
                    # fused fake-quant (DESIGN.md §13): completed pages
                    # round-trip through the page quantizer so the next
                    # chunk attends to exactly what the pool will store
                    cache = pages_mod.fake_quant_tree(cache, cache["len"][0],
                                                      sa, ps, kvd)
                return cache

            b1_sh = self._b1_shardings()
            self._chunk_jit[W] = jax.jit(
                chunk_fn, donate_argnums=(1,),
                in_shardings=(self._param_sh, b1_sh, None, None),
                out_shardings=b1_sh)
        with runtime.use_mesh(self.mesh):
            return self._chunk_jit[W](self.params, cache, chunk[None, :],
                                      jnp.int32(true_w))

    @telemetry.traced("engine.insert_slot")
    def insert_slot(self, batched_cache, slot_cache, slot: int):
        """Write a prefilled request into slot ``slot`` (donated, traced
        index: ONE compiled program covers every slot).  On the paged
        layout the host allocates the slot's pages first, then the B=1
        dense cache is scattered block-wise onto them (excess logical pages
        land on the scratch page — fixed write count, no recompiles)."""
        if self._paging_active:
            # the host knows this length already; the read-back blocks
            # until the request's last prefill program has run
            with telemetry.span("engine.insert.wait"):
                n_tok = int(np.asarray(slot_cache["len"])[0])
            with runtime.use_mesh(self.mesh):
                return self.paged_insert(batched_cache, slot_cache, slot,
                                         self._slot_axes(),
                                         self._slot_seq_axes(), n_tok)
        if self._slot_insert is None:
            self._slot_insert = slots_mod.make_slot_insert(
                self._slot_axes(),
                batched_sh=self._cache_shardings(jax.eval_shape(
                    lambda: api.init_cache(self.cfg, self._slot_count,
                                           self.max_len))),
                single_sh=self._b1_shardings())
        with runtime.use_mesh(self.mesh):
            return self._slot_insert(batched_cache, slot_cache,
                                     jnp.int32(slot))

    @telemetry.traced("engine.decode_slots")
    def decode_slots(self, cache, tokens, active, corrupt=None):
        """One masked batched decode step: every slot computes, only active
        slots advance (inactive cache leaves frozen).  Fixed shapes — the
        steady-state loop re-dispatches one compiled program forever.

        Returns ``(next_tokens, ok, cache)`` where ``ok`` is the per-slot
        finite-logits sentinel: False means that slot's logits went
        non-finite this step and its token is garbage — the scheduler
        quarantines it instead of appending.  ``corrupt`` (optional
        ``(n,)`` bool) is the fault-injection input: True slots get their
        logits NaN-poisoned inside the jitted step (all-False by default;
        fixed shape, zero extra recompiles).

        Paged layout: the host allocates any page the step will write into
        (position ``len``); then ``paged_attn="inplace"`` (default) appends
        each active slot's token to its page and attends DIRECTLY through
        the traced table (``api.paged_decode_step`` — no dense-view
        transient), while ``paged_attn="gather"`` keeps the reference
        discipline: gather the dense view, run the SAME family decode
        math, scatter the one new token per active slot back to its page.
        """
        n = int(tokens.shape[0])
        if corrupt is None:
            corrupt = np.zeros((n,), bool)
        if self._paging_active:
            act = np.asarray(active, bool)
            with runtime.use_mesh(self.mesh):
                cache = self.paged_pre_step(cache, act, self._slot_axes(),
                                            self._slot_seq_axes())
            if self._paged_step is None:
                ba, sa = self._slot_axes(), self._slot_seq_axes()
                rcfg = self._ragged_cfg
                paged_step = make_paged_step(rcfg, ba, sa, self._paged_attn)

                # explicit placements: pool head-cut, page table replicated
                # (host-owned), per-slot vectors on the batch axis — the
                # sharded jit cache stays keyed on ONE layout, so the
                # steady state never recompiles on a TP mesh either
                vec = self._vec_shardings(n)
                repl = NamedSharding(self.mesh, P())
                self._paged_step = jax.jit(
                    paged_step, donate_argnums=(1,),
                    in_shardings=(self._param_sh, self._pool_sh, repl,
                                  vec, vec, vec),
                    out_shardings=(vec, vec, self._pool_sh))
            table = self._pager.table()
            with (telemetry.span("engine.decode.dispatch"),
                  runtime.use_mesh(self.mesh)):
                out = self._paged_step(self.params, cache, table,
                                       jnp.asarray(tokens, jnp.int32),
                                       jnp.asarray(active, bool),
                                       jnp.asarray(corrupt, bool))
            self._pager.post_decode(act)
            return out
        self._meter_kv_read(np.asarray(active, bool))
        if n not in self._slot_step_jit:
            self._slot_step_jit[n] = step_mod.make_slot_step(
                self._ragged_cfg, self.mesh, self.params, cache,
                self._slot_axes(), cache_spec_fn=shd.serve_cache_pspecs,
                param_spec_fn=shd.serve_param_pspecs)
        with runtime.use_mesh(self.mesh):
            return self._slot_step_jit[n](
                self.params, cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(active, bool), jnp.asarray(corrupt, bool))

    def rebuild(self, n_slots: int):
        """Re-materialise every device-side byte from host state after a
        device loss: params re-placed from the host copy, a fresh page pool
        (or dense slot cache) allocated, and the host pager reset.

        What is deliberately NOT touched: the jit caches.  Compiled
        programs are immutable host artifacts under the split-brain
        contract — a device failure invalidates *buffers*, never code — so
        the rebuilt pool re-enters the SAME compiled step and recovery
        costs zero recompiles (serve_bench gates this).  The radix prefix
        index dies with the pool (its device bytes are gone); recovered
        requests republish as they re-prefill, so sharing re-forms among
        the survivors.
        """
        with runtime.use_mesh(self.mesh):
            self.params = jax.device_put(self.params, self._param_sh)
        return self.init_slot_cache(n_slots)

