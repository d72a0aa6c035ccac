"""Split-Brain serving engine — the paper's §IV-B protocol, executable.

Decoding is explicitly partitioned into:

  device_phase  — the ITA ASIC: stateless, LAQ-quantized linear projections
                  (QKV, FFN, LM head).  Zero dynamic state.
  host_phase    — the host CPU: KV-cache append, attention (the dynamic-
                  state op), residual adds, norm statistics, sampling.

Every tensor that crosses the boundary is registered on a TrafficMeter, so
the *measured* per-token interface bytes can be asserted equal to the
analytical TrafficModel (eq. 7-11) — that equality is a test
(tests/test_splitbrain.py) and a benchmark (table3_interface).

Two execution paths (DESIGN.md §1):

  jit=True (default) — parameters and the KV cache are stacked pytrees with
      a leading layer axis ``(L, ...)``; one ``jax.lax.scan`` sweeps the
      depth and the whole per-token step is a single jitted dispatch with
      donated cache buffers.  Boundary accounting happens at trace time:
      every crossing shape is static, so the meter is replayed host-side per
      token and stays byte-identical to the eager log.
  jit=False — the original per-layer Python loop, kept as the bit-level
      reference for parity tests and as the readable spec of the protocol.

``generate()`` fuses the *multi-token* loop too: prompt forcing plus greedy
decode run inside one jitted ``lax.scan`` — one dispatch per generation.

This engine covers the paper's own configs (decoder-only LM family); the
production serving path for all 10 archs is serve/engine.py.
"""
from __future__ import annotations

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
from repro.models import layers as L
from repro.serve import pages as pages_mod
from repro.serve import slots as slots_mod


def traffic_model_for(cfg: ModelConfig) -> TrafficModel:
    return TrafficModel.for_config(cfg)


def _stack_layers(tree, num_layers: int):
    """Collapse the (n_groups, group_size, ...) leading dims to (L, ...)."""
    return jax.tree.map(lambda a: a.reshape((num_layers,) + a.shape[2:]), tree)


class SplitBrainEngine(pages_mod.PagedEngineMixin):
    """Greedy decoding with an explicit host/device boundary."""

    def __init__(self, cfg: ModelConfig, params, max_len: int = 256,
                 quantize: bool = True, jit: bool = True,
                 kernels: Optional[str] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 paged_attn: str = "inplace", prefix_cache: str = "off",
                 kv_dtype: str = "bf16", mesh=None):
        if cfg.family != "lm" or len(cfg.layer_pattern) != 1:
            raise ValueError(
                "split-brain reference engine covers the paper's LM configs")
        if cfg.moe:
            raise ValueError(
                "split-brain reference engine covers dense FFNs")
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_test_mesh(
            jax.devices()[:1])
        # tensor-parallel degree of the serving mesh (DESIGN.md §11); tp == 1
        # (the 1-device test mesh) reproduces the single-device layout.
        self._tp = (int(self.mesh.shape[cfg.parallel.model_axis])
                    if cfg.parallel.model_axis in self.mesh.axis_names else 1)
        self.meter = TrafficMeter()
        # The "synthesis" step: weights become immutable INT4 codes.
        self.device_params = (api.quantize_model(params, cfg)
                              if quantize else params)
        self.host_params = params  # norms/embedding stay host-side floats
        self.max_len = max_len
        self.jit = jit
        # kernel set (kernels/ops.py): chosen once, from the mesh's platform
        self.kernels = kernels or ops.for_platform(
            self.mesh.devices.flat[0].platform)
        # -- static hoisting: everything derivable from cfg/params is computed
        #    once here, not per decode_token call.
        self._hd = cfg.resolved_head_dim
        self._dtype = jnp.dtype(cfg.dtype)
        self._n_layers = cfg.num_layers
        # Stacked (L, ...) layer pytrees: device-phase projections (possibly
        # QuantizedLinear codes+scales) and host-phase norm scales.  No
        # per-layer Python lists anywhere on the hot path.
        dev_blocks = self.device_params["blocks"]
        host_blocks = self.host_params["blocks"]
        self._weights = {
            "layers": {
                "attn": _stack_layers(dev_blocks["attn"], cfg.num_layers),
                "mlp": _stack_layers(dev_blocks["mlp"], cfg.num_layers),
                "ln_attn": _stack_layers(host_blocks["ln_attn"], cfg.num_layers),
                "ln_mlp": _stack_layers(host_blocks["ln_mlp"], cfg.num_layers),
            },
            "embed": self.host_params["embed"],
            "ln_final": self.host_params["ln_final"],
            "head": self.device_params.get("lm_head"),
        }
        # TP placement of the stacked weights: the Megatron column/row rules
        # match the stacked (L, ...) projections through their leading-dim
        # padding, "head" takes the lm_head column cut (DESIGN.md §11).
        # Quantized weights keep the FULL row+column cut — int32 matmul
        # accumulation is associative, so split contractions stay bitwise
        # exact.  Float weights (quantize=False) must fall back to the
        # column-only serve rules to preserve greedy token identity.
        spec_fn = shd.param_pspecs if quantize else shd.serve_param_pspecs
        self._param_sh = shd.with_sharding(
            self.mesh, spec_fn(self._weights, cfg, self.mesh))
        with runtime.use_mesh(self.mesh):
            self._weights = jax.device_put(self._weights, self._param_sh)
        self._cache_sh: Dict[int, Any] = {}      # keyed by batch size
        # Pre-computed per-token boundary-crossing byte counts (shapes are
        # static) for the trace-time meter replay; per batch element.
        self._decode_jit = jax.jit(self._token_step, donate_argnums=(1, 2))
        self._generate_jit: Dict[Tuple[int, int, Any], Any] = {}
        self._prefill_jit: Dict[int, Any] = {}   # keyed by bucket width
        self._slot_step = None
        self._slot_insert = None
        # paged slot cache (page_size=None keeps the dense slot layout)
        self.page_size = page_size
        self.num_pages = num_pages
        self._pager = (pages_mod.HostPager(page_size, num_pages, max_len)
                       if page_size is not None else None)
        self._paged_attn = self.check_paged_attn(paged_attn)
        self._prefix_cache_on = self.check_prefix_cache(prefix_cache)
        # pool storage format (DESIGN.md §13): int8/fp8 pages quantize on
        # write and dequantize at the attention page fetch
        self._kv_dtype = pages_mod.check_kv_dtype(kv_dtype, page_size)
        self._paging_active = self._pager is not None   # k/v always page
        self._paged_step = None
        self._b1_shape = None                  # B=1 request-cache eval_shape

    # ------------------------------------------------------------- device ops
    # The eager reference path: each helper registers its boundary crossing
    # on the meter at call time.
    def _device_qkv(self, layer_p, x):
        """ITA device: hardwired QKV projection (stateless)."""
        cfg = self.cfg
        self.meter.h2d("x_qkv_in", x.shape)
        q, k, v = L.qkv_project(layer_p["attn"], x, cfg.num_heads,
                                cfg.num_kv_heads, self._hd,
                                kernels=self.kernels)
        # K, V stream back to the host KV cache (eq. 7); Q accompanies them
        # in the same DMA (the paper counts K/V only — Q stays on-device in
        # the ASIC pipeline; we ship it because our "device" is a function).
        self.meter.d2h("kv_out", (2, *k.shape[:2], k.shape[2], k.shape[3]))
        return q, k, v

    def _device_attn_out(self, layer_p, attn):
        self.meter.h2d("attn_in", attn.shape)   # eq. 8
        return L.linear(attn, layer_p["attn"]["wo"], self.kernels)

    def _device_ffn(self, layer_p, y):
        out = L.swiglu(y, layer_p["mlp"]["w1"], layer_p["mlp"]["w3"],
                       layer_p["mlp"]["w2"], kernels=self.kernels)
        return out

    def _device_logits(self, x):
        head = self._weights["head"]
        logits = L.linear(x, head, self.kernels)
        self.meter.d2h("logits", logits.shape)   # eq. 9
        return logits

    @property
    def traffic_shards(self) -> int:
        """How many ways the boundary-traffic accounting splits per token.

        Equals the mesh's TP degree when every counted channel width
        (d_model, Hkv, Hq, vocab) divides exactly — each shard then crosses
        ``full/tp`` bytes and the per-shard entries sum to the single-device
        analytical model TO THE BYTE (DESIGN.md §11).  Any indivisible width
        falls back to 1 (single aggregate entry)."""
        cfg, tp = self.cfg, self._tp
        if (tp > 1 and cfg.d_model % tp == 0 and cfg.num_kv_heads % tp == 0
                and cfg.num_heads % tp == 0 and cfg.vocab_size % tp == 0):
            return tp
        return 1

    def _meter_token(self, batch: int) -> None:
        """Replay one token's boundary crossings on the meter.

        The jitted path cannot log from inside the trace, but every crossing
        shape is static, so this host-side replay is byte-identical (names,
        order, and sizes) to the eager path's runtime log.  On a TP mesh each
        crossing is logged once per model shard at ``width/tp``
        (``traffic_shards``): the host scatters each shard its activation
        slice and collects its KV-head/logit slice, so boundary bytes never
        duplicate across shards and every total — hence the eq. 7-10
        exactness contract — is unchanged.
        """
        cfg = self.cfg
        s = self.traffic_shards
        for _ in range(self._n_layers):
            for _ in range(s):
                self.meter.h2d("x_qkv_in", (batch, 1, cfg.d_model // s))
                self.meter.d2h("kv_out", (2, batch, cfg.num_kv_heads // s,
                                          1, self._hd))
                self.meter.h2d("attn_in", (batch, 1,
                                           cfg.num_heads * self._hd // s))
        for _ in range(s):
            self.meter.d2h("logits", (batch, 1, cfg.vocab_size // s))

    # --------------------------------------------------------- fused hot path
    def _layer_sweep(self, weights, k_cache, v_cache, pos, token, kv_attend):
        """The shared split-brain per-token body: embed, lax.scan the
        stacked layers (pre-norm -> DEVICE QKV -> rope -> the injected
        ``kv_attend`` -> DEVICE wo -> HOST residual -> DEVICE FFN), final
        norm, DEVICE head, HOST argmax.  ``kv_attend(k_all, v_all, li, q,
        k, v)`` is the ONLY point the dense and paged disciplines differ
        (cache append + attention at layer ``li``), so their token-identity
        contract cannot drift anywhere else.  The stacked caches ride in
        the scan's CARRY and are updated in place — slicing them as scan
        inputs and restacking them as outputs would copy every layer's
        cache out and back in each token (DESIGN.md §6).  Returns
        (next_tok, logits, new_k, new_v)."""
        cfg = self.cfg
        B = token.shape[0]
        hd = self._hd
        kn = self.kernels
        # HOST: embedding lookup (vocabulary table, random access)
        x = weights["embed"][token][:, None, :].astype(self._dtype)
        positions = pos[:, None]

        def layer_fn(carry, per_layer):
            x, k_all, v_all = carry
            p, li = per_layer
            # HOST: pre-norm (dynamic statistics)
            xn = L.rmsnorm(x, p["ln_attn"], cfg.norm_eps)
            # DEVICE: QKV projection
            q, k, v = L.qkv_project(p["attn"], xn, cfg.num_heads,
                                    cfg.num_kv_heads, hd, kernels=kn)
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
            # HOST: KV-cache append + attention (discipline-specific)
            attn, k_all, v_all = kv_attend(k_all, v_all, li, q, k, v)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads * hd)
            # DEVICE: output projection;  HOST: residual add
            x = x + L.linear(attn, p["attn"]["wo"], kn)
            # HOST norm -> DEVICE FFN -> HOST residual
            y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
            x = x + L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"],
                             p["mlp"]["w2"], kernels=kn)
            return (x, k_all, v_all), None

        n_layers = jax.tree.leaves(k_cache)[0].shape[0]
        (x, new_k, new_v), _ = jax.lax.scan(
            layer_fn, (x, k_cache, v_cache),
            (weights["layers"], jnp.arange(n_layers)))
        x = L.rmsnorm(x, weights["ln_final"], cfg.norm_eps)
        logits = L.linear(x, weights["head"], kn)[:, 0]
        # HOST: sampling
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, new_k, new_v

    def _token_step(self, weights, k_cache, v_cache, length, token):
        """One split-brain token, traceable: lax.scan over the stacked layers.

        k_cache/v_cache: (L, B, Hkv, S, hd).  Returns
        (next_tok, logits, new_k, new_v, new_length).
        """
        pos = length

        def kv_attend(k_all, v_all, li, q, k, v):
            kc = jax.vmap(lambda c, kk, i: jax.lax.dynamic_update_slice(
                c, kk, (0, i, 0)))(k_all[li], k[:, :, 0:1], pos)
            vc = jax.vmap(lambda c, vv, i: jax.lax.dynamic_update_slice(
                c, vv, (0, i, 0)))(v_all[li], v[:, :, 0:1], pos)
            attn = ops.decode_attention(q, kc, vc, pos + 1,
                                        softcap=self.cfg.softcap)
            return attn, k_all.at[li].set(kc), v_all.at[li].set(vc)

        next_tok, logits, new_k, new_v = self._layer_sweep(
            weights, k_cache, v_cache, pos, token, kv_attend)
        return next_tok, logits, new_k, new_v, length + 1

    def _paged_token_step(self, weights, k_pool, v_pool, table, length,
                          token, write):
        """One split-brain token computed THROUGH the page pool — no dense
        view.  k_pool/v_pool: (L, num_pages, page_size, Hkv/f, f·hd) in
        the stored, possibly lane-folded layout (``layers.kv_fold``),
        carried through the same ``_layer_sweep`` as ``_token_step`` and
        touched in place at each layer's index; the HOST phase appends each
        active slot's K/V to its page (inactive slots land on scratch) and
        attention walks ``pool[table]`` page-block-wise
        (``ops.paged_decode_attention``), so steady-state KV reads are
        O(live tokens) per slot.  Returns
        (next_tok, logits, new_k_pool, new_v_pool, new_length).
        """
        pos = length

        def kv_attend(kc, vc, li, q, k, v):
            kc = L.paged_cache_write(kc, k, table, pos, write, layer=li)
            vc = L.paged_cache_write(vc, v, table, pos, write, layer=li)
            attn = ops.paged_decode_attention(
                q, kc, vc, table, pos + 1, softcap=self.cfg.softcap,
                kernels=self.kernels,
                model_axis=self.cfg.parallel.model_axis,
                batch_axes=self.cfg.parallel.batch_axes, layer=li)
            return attn, kc, vc

        next_tok, logits, new_k, new_v = self._layer_sweep(
            weights, k_pool, v_pool, pos, token, kv_attend)
        return (next_tok, logits, new_k, new_v,
                length + write.astype(jnp.int32))

    def _generate_fn(self, steps: int, max_out: int, eos_id: Optional[int]):
        """Build the fused multi-token loop: prompt forcing + greedy decode
        inside one lax.scan — a single dispatch per generation.

        ``steps``/``max_out`` are power-of-two buckets; the actual prompt
        length ``T0`` is a TRACED argument, so one compiled program serves
        every prompt length in the bucket (the jit cache is O(log max_len)).
        With ``eos_id``, a stream that emits the stop token stops counting
        (``gen_len`` freezes, later outputs pad with ``eos_id``) while the
        scan keeps lockstep — identical semantics to the serve engine loop.
        """

        def gen(weights, k_cache, v_cache, length, prompts, T0, total):
            B = prompts.shape[0]
            W = prompts.shape[1]

            def body(carry, t):
                k, v, ln, tok, alive, n = carry
                nxt, _, k2, v2, ln2 = self._token_step(weights, k, v, ln, tok)
                # ``total`` = T0-1+max_new (traced): the bucket may run more
                # scan steps than the request asked for, but the cache must
                # come back in EXACTLY the prompt+max_new state (and never
                # clamp-write past max_len), so the extras are frozen out.
                run = t < total
                k = jnp.where(run, k2, k)
                v = jnp.where(run, v2, v)
                ln = jnp.where(run, ln2, ln)
                is_gen = (t >= T0 - 1) & run   # ys[T0-1:] = generated region
                if eos_id is None:
                    emitted = nxt
                else:
                    emitted = jnp.where(alive | ~is_gen, nxt,
                                        jnp.int32(eos_id))
                n = n + (is_gen & alive).astype(jnp.int32)
                if eos_id is not None:
                    alive = alive & ~(is_gen & (emitted == eos_id))
                # teacher-force the remaining prompt tokens, then free-run
                forced = jax.lax.dynamic_slice_in_dim(
                    prompts, jnp.minimum(t + 1, W - 1), 1, axis=1)[:, 0]
                tok = jnp.where(t + 1 < T0, forced, emitted)
                return (k, v, ln, tok, alive, n), emitted

            carry = (k_cache, v_cache, length, prompts[:, 0],
                     jnp.ones((B,), bool), jnp.zeros((B,), jnp.int32))
            (k, v, ln, _, _, n), ys = jax.lax.scan(body, carry,
                                                   jnp.arange(steps))
            # ys[t] is the token produced after consuming input t; outputs
            # from step T0-1 onward are the generated continuation.
            toks = jax.lax.dynamic_slice_in_dim(ys.T, T0 - 1, max_out, axis=1)
            return toks, k, v, ln, n

        return jax.jit(gen, donate_argnums=(1, 2))

    # --------------------------------------------------------------- decoding
    def decode_token(self, cache: Dict[str, Any], token: jnp.ndarray):
        """One token through the split-brain loop. token: (B,).

        The jitted path donates the cache buffers: use the *returned* cache,
        the one passed in is consumed.
        """
        if not self.jit:
            return self.decode_token_eager(cache, token)
        self._meter_token(token.shape[0])
        with runtime.use_mesh(self.mesh):
            next_tok, logits, k, v, length = self._decode_jit(
                self._weights, cache["k"], cache["v"], cache["len"], token)
        return next_tok, logits, {"k": k, "v": v, "len": length}

    def decode_token_eager(self, cache: Dict[str, Any], token: jnp.ndarray):
        """The reference per-layer Python loop (meter logs at runtime)."""
        cfg = self.cfg
        B = token.shape[0]
        hd = self._hd
        x = self._weights["embed"][token][:, None, :].astype(self._dtype)
        pos = cache["len"]
        positions = pos[:, None]

        new_k, new_v = [], []
        for layer in range(self._n_layers):
            p = jax.tree.map(lambda a: a[layer], self._weights["layers"])
            # HOST: pre-norm (dynamic statistics)
            xn = L.rmsnorm(x, p["ln_attn"], cfg.norm_eps)
            # DEVICE: QKV projection
            q, k, v = self._device_qkv(p, xn)
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
            # HOST: KV-cache append + attention
            kc, vc = cache["k"][layer], cache["v"][layer]
            kc = jax.vmap(lambda c, kk, i: jax.lax.dynamic_update_slice(
                c, kk, (0, i, 0)))(kc, k[:, :, 0:1], pos)
            vc = jax.vmap(lambda c, vv, i: jax.lax.dynamic_update_slice(
                c, vv, (0, i, 0)))(vc, v[:, :, 0:1], pos)
            attn = ops.decode_attention(q, kc, vc, pos + 1,
                                        softcap=cfg.softcap)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads * hd)
            # DEVICE: output projection;  HOST: residual add
            x = x + self._device_attn_out(p, attn)
            # HOST norm -> DEVICE FFN -> HOST residual
            y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
            x = x + self._device_ffn(p, y)
            new_k.append(kc)
            new_v.append(vc)

        x = L.rmsnorm(x, self._weights["ln_final"], cfg.norm_eps)
        logits = self._device_logits(x)[:, 0]
        # HOST: sampling
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, {"k": jnp.stack(new_k), "v": jnp.stack(new_v),
                                  "len": cache["len"] + 1}

    def generate(self, prompts, max_new: int = 16,
                 eos_id: Optional[int] = None) -> Dict[str, Any]:
        """Greedy-decode a batch in ONE dispatch. prompts: (B, T0) int32.

        Prompt tokens are teacher-forced through the same per-token step
        (filling the KV cache), then ``max_new`` tokens free-run — all
        inside a single jitted lax.scan.  ``decode_s``/``tokens_per_s``
        cover the whole dispatch (prompt + decode), the same scope the
        stepwise reference times.

        Compiled shapes are bucketed (prompt width / step count to powers of
        two, T0 traced), so the jit cache is O(log max_len).  ``eos_id``
        enables per-request stop tokens: rows pad with ``eos_id`` past each
        stop and ``gen_len`` reports exact generated lengths; the meter then
        replays boundary bytes per *active* token only.
        """
        prompts = jnp.asarray(prompts, jnp.int32)
        B, T0 = prompts.shape
        if T0 - 1 + max_new > self.max_len:
            raise ValueError(
                f"request does not fit the cache: prompt_len={T0} + "
                f"max_new={max_new} needs {T0 - 1 + max_new} positions but "
                f"max_len={self.max_len}")
        if not self.jit:
            return self._generate_stepwise(prompts, max_new, eos_id)
        Pb = slots_mod.bucket(T0)
        Mb = slots_mod.bucket(max_new)
        Sb = slots_mod.bucket(Pb - 1 + Mb)
        key = (Pb, Mb, eos_id)
        if key not in self._generate_jit:
            self._generate_jit[key] = self._generate_fn(Sb, Mb, eos_id)
        if Pb > T0:
            prompts = jnp.pad(prompts, ((0, 0), (0, Pb - T0)))
        cache = self.init_cache(B)
        t0 = time.perf_counter()
        with runtime.use_mesh(self.mesh):
            toks, k, v, length, n = self._generate_jit[key](
                self._weights, cache["k"], cache["v"], cache["len"], prompts,
                jnp.int32(T0), jnp.int32(T0 - 1 + max_new))
        toks = jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        toks = np.asarray(toks)[:, :max_new]
        gen_len = np.minimum(np.asarray(n), max_new)
        # Boundary accounting, per ACTIVE token: every prompt-forcing step
        # crosses for the whole batch; decode step t crosses only for the
        # streams still running (eos_id=None -> all of them, the pre-EOS
        # behaviour byte-for-byte).
        for _ in range(T0 - 1):
            self._meter_token(B)
        for t in range(max_new):
            a = int((gen_len > t).sum())
            if a:
                self._meter_token(a)
        return {"tokens": toks,
                "gen_len": gen_len,
                "cache": {"k": k, "v": v, "len": length},
                "tokens_per_s": int(gen_len.sum()) / dt,
                "decode_s": dt}

    def _generate_stepwise(self, prompts: jnp.ndarray, max_new: int,
                           eos_id: Optional[int] = None):
        """Token-at-a-time reference generation (eager decode loop).

        Timed over the WHOLE generation (prompt forcing + decode), same
        scope as the fused path's single dispatch, so the two tokens/s
        figures are directly comparable.  EOS semantics mirror the fused
        loop (finished rows emit/feed ``eos_id``, may break early once all
        rows stop); NOTE the eager meter logs at runtime, so it counts every
        executed lockstep step for the full batch — the per-active-token
        accounting is a property of the replayed (jit) paths.
        """
        B, T0 = prompts.shape
        cache = self.init_cache(B)
        tok = prompts[:, 0]
        outs = []
        alive = np.ones((B,), bool)
        gen_len = np.zeros((B,), np.int32)
        t0 = time.perf_counter()
        for t in range(1, T0):
            _, _, cache = self.decode_token_eager(cache, tok)
            tok = prompts[:, t]
        for _ in range(max_new):
            tok, _, cache = self.decode_token_eager(cache, tok)
            emitted = np.asarray(tok)
            gen_len += alive
            if eos_id is not None:
                emitted = np.where(alive, emitted, eos_id)
                alive &= emitted != eos_id
                tok = jnp.asarray(emitted, jnp.int32)
            outs.append(emitted)
            if eos_id is not None and not alive.any():
                break
        dt = time.perf_counter() - t0
        while len(outs) < max_new:
            outs.append(np.full((B,), eos_id, np.int32))
        return {"tokens": np.stack(outs, 1), "cache": cache,
                "gen_len": gen_len,
                "tokens_per_s": int(gen_len.sum()) / dt, "decode_s": dt}

    def _cache_like(self, batch: int) -> Dict[str, Any]:
        """ShapeDtypeStruct pytree of the stacked (L, B, Hkv, S, hd) cache."""
        cfg = self.cfg
        shape = (cfg.num_layers, batch, cfg.num_kv_heads, self.max_len,
                 self._hd)
        return {
            "k": jax.ShapeDtypeStruct(shape, self._dtype),
            "v": jax.ShapeDtypeStruct(shape, self._dtype),
            "len": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }

    def _cache_shardings(self, batch: int):
        """NamedSharding pytree for the stacked cache under the serve rules
        (head-cut KV; identical to replicated on a 1-device mesh)."""
        if batch not in self._cache_sh:
            self._cache_sh[batch] = shd.with_sharding(
                self.mesh, shd.serve_cache_pspecs(
                    self._cache_like(batch), self.cfg, self.mesh))
        return self._cache_sh[batch]

    def _vec_shardings(self, n: int) -> NamedSharding:
        """Placement of a per-slot (n,) vector (tokens / active mask)."""
        ax = shd.MeshAxes(self.mesh, self.cfg)
        b = ax.resolve("batch")
        if b is None or n % ax.size(b) != 0:
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, P(b))

    def init_cache(self, batch: int) -> Dict[str, Any]:
        """Stacked KV cache: (L, B, Hkv, S, hd) — scan-sweepable, no lists.
        Allocated directly into its TP placement (no full replica ever
        materialises on a multi-device mesh)."""
        like = self._cache_like(batch)
        sh = self._cache_shardings(batch)
        with runtime.use_mesh(self.mesh):
            return jax.tree.map(
                lambda a, s: jnp.zeros(a.shape, a.dtype, device=s), like, sh)

    # ---------------------------------------------------------- slot protocol
    # Consumed by serve/scheduler.py: the stacked cache doubles as a slot
    # cache — slot i is batch row i, at its own ragged position.  With
    # ``page_size`` set, the (L, B, Hkv, S, hd) K/V leaves instead live in a
    # shared page pool behind a host-owned page table (serve/pages.py).
    _SLOT_AXES = {"k": 1, "v": 1, "len": 0}
    _SEQ_AXES = {"k": 3, "v": 3, "len": -1}

    def init_slot_cache(self, n_slots: int) -> Dict[str, Any]:
        shape = self._cache_like(n_slots)
        ba, sa = self._SLOT_AXES, self._SEQ_AXES
        self._note_slot_cache(n_slots, shape, ba, sa)
        if not self._paging_active:
            return self.init_cache(n_slots)
        pool = self._pager.reset(n_slots)
        self._pager.prefix_on = self.prefix_sharing_active()
        # head-cut pool placement (DESIGN.md §11): each model shard owns
        # Hkv/tp kv heads, (L, num_pages, ps, Hkv/(f*tp), f*hd) of the folded
        # pool (the fold keeps that cut wherever tp divides Hkv); head rows
        # the TP degree does not divide auto-replicate (the Hkv < tp
        # fallback) and the per-shard byte accounting stays 1-way.
        pshape = pages_mod.pool_shape(shape, ba, sa, pool.num_pages,
                                      self.page_size, self._kv_dtype,
                                      self._tp)
        pool_specs = shd.pool_pspecs(pshape, self.cfg, self.mesh, sa)
        self._pool_sh = shd.with_sharding(self.mesh, pool_specs)
        self._b1_sh = self._cache_shardings(1)
        self._note_slot_cache(n_slots, shape, ba, sa,
                              shd.pool_kv_cut(pool_specs, sa, self._tp,
                                              self.cfg.parallel.model_axis))
        self._kv_quant_tok_bytes = (
            pages_mod.kv_token_bytes_quant(shape, ba, sa, self.page_size,
                                           self._kv_dtype)
            if self._kv_dtype != "bf16" else None)
        with runtime.use_mesh(self.mesh):
            return pages_mod.make_pool(shape, ba, sa, pool.num_pages,
                                       self.page_size,
                                       shardings=self._pool_sh,
                                       kv_dtype=self._kv_dtype, tp=self._tp)

    # reserve_slot / can_ever_admit / free_slot / cache_stats come from
    # pages_mod.PagedEngineMixin.
    def _stats_seq_axes(self):
        return self._SEQ_AXES

    def rebuild(self, n_slots: int) -> Dict[str, Any]:
        """Re-materialise every device-side byte from host state after a
        device loss: weights re-placed from the host copy, a fresh page
        pool (or dense slot cache) allocated, host pager reset.  The jit
        caches are deliberately kept — compiled programs are immutable
        host artifacts (a device failure invalidates buffers, never code),
        so the rebuilt pool re-enters the SAME compiled step and recovery
        costs zero recompiles."""
        with runtime.use_mesh(self.mesh):
            self._weights = jax.device_put(self._weights, self._param_sh)
        return self.init_slot_cache(n_slots)

    def new_request_cache(self) -> Dict[str, Any]:
        """Fresh B=1 cache for chunked prefill (slot-shaped, empty)."""
        return self.init_cache(1)

    def seed_request_cache(self, cache, slot: int, cached_len: int):
        """Prefix-aware prefill entry: B=1 request cache seeded with the
        slot's matched prefix pages gathered from the pool, ``len`` set to
        ``cached_len`` — the tail chunk stream continues from there."""
        if self._b1_shape is None:
            self._b1_shape = self._cache_like(1)
        with runtime.use_mesh(self.mesh):
            return self.paged_seed(cache, slot, cached_len, self._SLOT_AXES,
                                   self._SEQ_AXES, self._b1_shape)

    def prefill_chunk_slot(self, cache: Dict[str, Any], chunk: np.ndarray,
                           true_w: int) -> Dict[str, Any]:
        """Advance a B=1 request cache by one right-padded prompt chunk.

        Reuses the bucketed prefill program (it scans the split-brain token
        step from WHATEVER state the cache is in, freezing past
        ``true_w``), so chunked prefill adds zero new compiled programs
        beyond the one chunk width.
        """
        chunk = np.asarray(chunk, np.int32)
        W = chunk.shape[0]
        pages_mod.check_chunk_width(W, self.max_len)
        if W not in self._prefill_jit:
            self._prefill_jit[W] = self._prefill_fn(W)
        with runtime.use_mesh(self.mesh):
            k, v, ln = self._prefill_jit[W](
                self._weights, cache["k"], cache["v"], cache["len"],
                jnp.asarray(chunk[None, :]), jnp.int32(true_w))
        return {"k": k, "v": v, "len": ln}

    def _prefill_fn(self, width: int):
        """Bucketed B=1 prompt prefill: scan the split-brain token step over
        the padded width, freezing state past ``true_len`` (traced)."""

        def prefill(weights, k, v, ln, tokens, true_len):
            def body(carry, t):
                k, v, ln = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1,
                                                   axis=1)[:, 0]
                _, _, k2, v2, ln2 = self._token_step(weights, k, v, ln, tok)
                keep = t < true_len
                return (jnp.where(keep, k2, k), jnp.where(keep, v2, v),
                        jnp.where(keep, ln2, ln)), None

            (k, v, ln), _ = jax.lax.scan(body, (k, v, ln),
                                         jnp.arange(width))
            if self._kv_dtype != "bf16":
                # fused fake-quant (DESIGN.md §13): completed pages
                # round-trip through the page quantizer, so the chunk
                # stream attends to exactly what pool insertion will store
                c = pages_mod.fake_quant_tree(
                    {"k": k, "v": v}, ln[0], {"k": 3, "v": 3},
                    self.page_size, self._kv_dtype)
                k, v = c["k"], c["v"]
            return k, v, ln

        b1 = self._cache_shardings(1)
        return jax.jit(
            prefill, donate_argnums=(1, 2),
            in_shardings=(self._param_sh, b1["k"], b1["v"], b1["len"],
                          None, None),
            out_shardings=(b1["k"], b1["v"], b1["len"]))

    def prefill_slot(self, prompt: np.ndarray):
        """Prefill ONE request into a fresh B=1 cache (bucketed width).

        prompt (T0,) -> (slot-shaped cache with len = T0-1, input token for
        the next decode step).  Compiles once per power-of-two width.
        """
        prompt = np.asarray(prompt, np.int32)
        T0 = prompt.shape[0]
        cache = self.init_cache(1)
        if T0 > 1:
            width = slots_mod.bucket(T0 - 1)
            if width not in self._prefill_jit:
                self._prefill_jit[width] = self._prefill_fn(width)
            body = np.zeros((1, width), np.int32)
            body[0, :T0 - 1] = prompt[:-1]
            with runtime.use_mesh(self.mesh):
                k, v, ln = self._prefill_jit[width](
                    self._weights, cache["k"], cache["v"], cache["len"],
                    jnp.asarray(body), jnp.int32(T0 - 1))
            cache = {"k": k, "v": v, "len": ln}
        return cache, int(prompt[-1])

    def insert_slot(self, batched_cache, slot_cache, slot: int):
        """Write a prefilled request into slot ``slot`` (donated batched
        buffers, traced index: ONE compiled program covers every slot).  On
        the paged layout the host allocates the slot's pages first and the
        B=1 K/V is scattered block-wise onto them."""
        if self._paging_active:
            n_tok = int(np.asarray(slot_cache["len"])[0])
            with runtime.use_mesh(self.mesh):
                return self.paged_insert(batched_cache, slot_cache, slot,
                                         self._SLOT_AXES, self._SEQ_AXES,
                                         n_tok)
        if self._slot_insert is None:
            self._slot_insert = slots_mod.make_slot_insert(
                self._SLOT_AXES,
                batched_sh=self._cache_shardings(self._slot_count),
                single_sh=self._cache_shardings(1))
        with runtime.use_mesh(self.mesh):
            return self._slot_insert(batched_cache, slot_cache,
                                     jnp.int32(slot))

    def decode_slots(self, cache: Dict[str, Any], tokens, active,
                     corrupt=None):
        """One masked batched split-brain token step: every slot computes,
        only ``active`` slots advance (K/V and ``len`` frozen elsewhere).
        Fixed (max_slots, ...) shapes — zero recompiles in steady state.
        Returns ``(next_tokens, ok, cache)``: ``ok`` is the per-slot
        finite-logits sentinel and ``corrupt`` (optional ``(n,)`` bool)
        NaN-poisons the flagged slots' logits inside the jitted step (the
        fault-injection hook; all-False default, zero extra recompiles).
        Paged layout: host allocates the page position ``len`` falls in;
        ``paged_attn="inplace"`` (default) appends K/V to the pages and
        attends directly through the traced table (``_paged_token_step`` —
        no dense-view transient), ``paged_attn="gather"`` keeps the
        reference discipline (gather K/V through the table, same token
        step, scatter one token back per active slot)."""
        n = int(np.asarray(tokens).shape[0])
        if corrupt is None:
            corrupt = np.zeros((n,), bool)
        if self._paging_active:
            act = np.asarray(active, bool)
            with runtime.use_mesh(self.mesh):
                cache = self.paged_pre_step(cache, act, self._SLOT_AXES,
                                            self._SEQ_AXES)
            if self._paged_step is None:
                ba, sa = self._SLOT_AXES, self._SEQ_AXES

                if self._paged_attn == "inplace":
                    def paged_step(weights, pcache, table, tok, act_m, bad):
                        _, logits, k2, v2, ln2 = self._paged_token_step(
                            weights, pcache["k"], pcache["v"], table,
                            pcache["len"], tok, act_m)
                        logits = slots_mod.corrupt_logits(logits, bad)
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        ok = slots_mod.finite_logits(logits)
                        return nxt, ok, {"k": k2, "v": v2, "len": ln2}
                else:
                    def paged_step(weights, pcache, table, tok, act_m, bad):
                        view = pages_mod.gather_tree(pcache, table, ba, sa,
                                                     self._hd)
                        pos = view["len"]
                        _, logits, k2, v2, ln2 = self._token_step(
                            weights, view["k"], view["v"], pos, tok)
                        logits = slots_mod.corrupt_logits(logits, bad)
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        ok = slots_mod.finite_logits(logits)
                        new = {"k": k2, "v": v2,
                               "len": jnp.where(act_m, ln2, pos)}
                        pc = pages_mod.scatter_token_tree(
                            pcache, new, table, pos, act_m, ba, sa)
                        return nxt, ok, pc

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
            with runtime.use_mesh(self.mesh):
                nxt, ok, pc = self._paged_step(
                    self._weights, cache, self._pager.table(),
                    jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(active, bool),
                    jnp.asarray(corrupt, bool))
            self._pager.post_decode(act)
            return nxt, ok, pc
        self._meter_kv_read(np.asarray(active, bool))
        if self._slot_step is None:
            def slot_step(weights, k, v, ln, tok, active, bad):
                _, logits, k2, v2, ln2 = self._token_step(weights, k, v, ln,
                                                          tok)
                logits = slots_mod.corrupt_logits(logits, bad)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                ok = slots_mod.finite_logits(logits)
                m = active[None, :, None, None, None]   # (L, B, Hkv, S, hd)
                return (nxt, ok, jnp.where(m, k2, k), jnp.where(m, v2, v),
                        jnp.where(active, ln2, ln))

            sh = self._cache_shardings(self._slot_count)
            vec = self._vec_shardings(n)
            self._slot_step = jax.jit(
                slot_step, donate_argnums=(1, 2),
                in_shardings=(self._param_sh, sh["k"], sh["v"], sh["len"],
                              vec, vec, vec),
                out_shardings=(vec, vec, sh["k"], sh["v"], sh["len"]))
        with runtime.use_mesh(self.mesh):
            nxt, ok, k, v, ln = self._slot_step(
                self._weights, cache["k"], cache["v"], cache["len"],
                jnp.asarray(tokens, jnp.int32), jnp.asarray(active, bool),
                jnp.asarray(corrupt, bool))
        return nxt, ok, {"k": k, "v": v, "len": ln}

    def meter_tokens(self, n: int) -> None:
        """Replay ``n`` active tokens' boundary crossings (scheduler hook)."""
        if int(n) > 0:
            self._meter_token(int(n))

    def measured_bytes_per_token(self, batch: int = 1,
                                 count_q: bool = False) -> Dict[str, int]:
        """Per-token boundary bytes from the meter (per sequence).

        The paper's eq. 10 counts K/V out, attention in, logits out; our
        meter additionally logs the QKV input activation (h2d "x_qkv_in").
        ``count_q=False`` reproduces the paper's accounting exactly.
        """
        tot = self.meter.measured_bytes(count_q)
        return {k: v // batch for k, v in tot.items()}
