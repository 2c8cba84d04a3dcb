"""KV-cache migration: prefill output -> DCP-placed pool frames (§3 (2)-(3)).

After external prefill, the control plane allocates target KV space per the
WaterFill split and triggers the physical transfer into each KV-binding
instance's pool.  Token->shard assignment is contiguous ranges in sorted
binding order (decode attention + LSE merge are order-agnostic over the
prefix, so any partition is exact).

Two implementations:

  * ``load_prefill_*`` — host-side (numpy) writes into the global pool
    arrays; the caller uploads the pools afterwards.  Reference semantics,
    used by the equivalence tests and the standalone integration scripts.
  * ``PrefillScatter`` — jitted on-device scatters.  The serve state never
    leaves the device: prefill KV (already device-resident from the prefill
    forward pass) is written into the pools by a donated scatter driven by
    small int32 coordinate tensors.  All requests admitted in one scheduler
    step batch into ONE scatter call per state kind.  This is the engine's
    hot path (no state device->host->device round trip).
"""
from __future__ import annotations

import numpy as np

from ..configs.base import ModelConfig
from .dcp import DecodeDims, attn_tp_geometry, kv_group_size
from .state import ClusterState


def attn_layer_index(cfg: ModelConfig, attn_ordinal: int) -> tuple[int, int]:
    """ordinal among attention layers -> (block index, position within block)."""
    pattern = cfg.block_pattern()
    per_block = sum(1 for k in pattern if k["mixer"] == "attn")
    return attn_ordinal // per_block, attn_ordinal % per_block


def shard_ranges(cluster: ClusterState, rid: int) -> list[tuple[int, int, int]]:
    """[(instance, start_token, num_tokens)] contiguous split of the prefix."""
    shards = cluster.page_table.shard_tokens(rid)
    out, start = [], 0
    for s in sorted(shards):
        t = shards[s]
        if t > 0:
            out.append((s, start, t))
            start += t
    return out


def load_prefill_kv(cfg: ModelConfig, cluster: ClusterState, dims: DecodeDims,
                    state_np: dict, rid: int, kv_layers) -> None:
    """Write one request's prefill KV into the (numpy) pool arrays.

    kv_layers: per attention layer, (k [len, Hkv, hd], v [len, Hkv, hd]) or
    (c_kv [len, kvr], k_rope [len, dr]) for MLA.
    """
    page = dims.page
    pt = cluster.page_table
    ranges = shard_ranges(cluster, rid)
    _, khs, ps = attn_tp_geometry(cfg, dims.tp)
    kg = kv_group_size(cfg, dims.tp)

    # hybrid sub-pool addressing: frame f of kv-head group h lives in
    # sub-pool chunk c = (f % ps)*khs + h at local frame f // ps; the chunk
    # stores its kg = Hkv/khs heads flattened into the last dim (core/dcp.py)
    for a, kv in enumerate(kv_layers):
        bi, pos = attn_layer_index(cfg, a)
        if cfg.is_mla:
            c_kv, k_rope = kv
            lat = np.concatenate([np.asarray(c_kv, np.float32),
                                  np.asarray(k_rope, np.float32)], axis=-1)
            # [nb, na, I, tp, F', page, lanes]: the latent fills the first
            # dk lanes of a row stored at whole 128-lane tiles
            pool = state_np["kv_pool"]
            dk = lat.shape[-1]
            for s, start, t in ranges:
                frames = pt.shard_frames(rid, s)
                for j in range(t):
                    f, o = frames[j // page], j % page
                    pool[bi, pos, s, (f % ps) * khs, f // ps, o, :dk] = \
                        lat[start + j]
        else:
            k, v = kv
            k = np.asarray(k, np.float32)
            v = np.asarray(v, np.float32)
            kp, vp = state_np["k_pool"], state_np["v_pool"]
            for s, start, t in ranges:
                frames = pt.shard_frames(rid, s)
                for j in range(t):
                    f, o = frames[j // page], j % page
                    for h in range(khs):
                        c = (f % ps) * khs + h
                        grp = slice(h * kg, (h + 1) * kg)
                        kp[bi, pos, s, c, f // ps, o] = \
                            k[start + j, grp].reshape(-1)
                        vp[bi, pos, s, c, f // ps, o] = \
                            v[start + j, grp].reshape(-1)


def load_prefill_ssm(cfg: ModelConfig, state_np: dict, instance: int,
                     slot: int, ssm_layers) -> None:
    """Write one request's final prefill SSM states into its decode slot.

    ssm_layers: per SSM layer, (conv_state [cw-1, conv_dim], h [nh, hd, ns]).
    """
    din, ns = cfg.ssm_d_inner, cfg.ssm_state
    pattern = cfg.block_pattern()
    per_block = sum(1 for k in pattern if k["mixer"] == "ssm")
    for si, (conv, h) in enumerate(ssm_layers):
        bi, pos = si // per_block, si % per_block
        conv = np.asarray(conv, np.float32)
        state_np["conv_x"][bi, pos, instance, slot] = conv[:, :din]
        state_np["conv_B"][bi, pos, instance, slot] = conv[:, din:din + ns]
        state_np["conv_C"][bi, pos, instance, slot] = conv[:, din + ns:]
        state_np["ssm_state"][bi, pos, instance, slot] = np.asarray(h, np.float32)


# --------------------------------------------------------------------------- #
# on-device prefill loading (the engine's host-free hot path)
# --------------------------------------------------------------------------- #
def prefill_coords(cluster: ClusterState, rid: int, page: int,
                   ps: int) -> np.ndarray:
    """Per-token pool coordinates for one request's prefix, token order.

    Returns int32 [4, T]: (instance, stripe = f %% ps, sub_frame = f // ps,
    offset) — exactly the hybrid sub-pool addressing of the numpy loaders.
    """
    pt = cluster.page_table
    cols = []
    for s, start, t in shard_ranges(cluster, rid):
        frames = np.asarray(pt.shard_frames(rid, s), dtype=np.int64)
        j = np.arange(t)
        f = frames[j // page]
        cols.append(np.stack([np.full(t, s), f % ps, f // ps, j % page]))
    if not cols:
        return np.zeros((4, 0), np.int32)
    return np.concatenate(cols, axis=1).astype(np.int32)


class PrefillScatter:
    """Jitted, donated scatters loading prefill output into the serve state.

    One compiled executable per padded token-count bucket (``_quantize_dim``
    ladder keeps the shape family bounded; ``jax.jit`` specializes per
    shape); padding rows carry ``instance = I`` and are dropped by the
    scatter (``mode='drop'``).  The state argument is donated and the
    output shardings are pinned to the state's own, so steady-state
    admission reuses the pool buffers in place.
    """

    def __init__(self, cfg: ModelConfig, dims: DecodeDims,
                 num_instances: int):
        self.cfg = cfg
        self.dims = dims
        self.I = num_instances
        _, self.khs, self.ps = attn_tp_geometry(cfg, dims.tp)
        self.kg = kv_group_size(cfg, dims.tp)
        self._fns: dict = {}
        self._state_shardings: dict | None = None

    def _out_shardings(self, state: dict) -> dict:
        """Pin scatter outputs to the serve state's own shardings: without
        this, GSPMD may pick a different output layout (e.g. model-sharding
        the SSM conv dims), which both breaks donation aliasing and
        mismatches the AOT step executable's compiled input shardings."""
        if self._state_shardings is None:
            self._state_shardings = {k: v.sharding for k, v in state.items()}
        return {k: self._state_shardings[k] for k in state}

    def _jit(self, kind: str, body, state: dict):
        """One donated jitted fn per scatter kind (jit re-specializes per
        padded bucket shape, so the executable family stays bounded)."""
        fn = self._fns.get(kind)
        if fn is None:
            import jax
            fn = jax.jit(body, donate_argnums=(0,),
                         out_shardings=self._out_shardings(state))
            self._fns[kind] = fn
        return fn

    # -- bucketing ---------------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        from .routing import _quantize_dim
        return _quantize_dim(max(n, 1))

    @staticmethod
    def _pad_to(x, axis: int, n: int):
        """Zero-pad ``x`` along ``axis`` up to length n (no-op if equal)."""
        if x is None or x.shape[axis] == n:
            return x
        import jax.numpy as jnp
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, n - x.shape[axis])
        return jnp.pad(x, pad)

    def _pad_coords(self, coords: np.ndarray, nb: int):
        """Pad [k, n] coords to n=nb with out-of-range instance ids."""
        import jax.numpy as jnp
        k, n = coords.shape
        pad = np.full((k, nb - n), 0, np.int32)
        pad[0] = self.I                               # dropped by the scatter
        return jnp.asarray(np.concatenate([coords, pad], axis=1))

    # -- attention KV ------------------------------------------------------
    def _quantized_scatter(self, pool, sc, x, ii, c, ff, oo, off):
        """Scatter ``x`` into a quantized pool + its per-page scale sidecar.

        Implements the offset-0 rule (kernels/quant.py) in one fused body:
        pages receiving an offset-0 token in THIS call get a fresh scale =
        the scatter-max of the call's per-token amax/qmax for that page;
        every other written page keeps its scale and the new tokens clip
        into it.  Pad rows (instance = I) drop from both scatters.
        """
        import jax.numpy as jnp
        from ..kernels import quant
        kv_dtype = self.dims.kv_dtype
        tok = quant.amax_scale(x, kv_dtype)                  # [nb,na,T,khs]
        fresh = jnp.zeros_like(sc).at[:, :, ii, c, ff].max(tok, mode="drop")
        has0 = (jnp.zeros(sc.shape[2:], jnp.int32).at[ii, c, ff].max(
            jnp.broadcast_to((off == 0)[:, None].astype(jnp.int32), c.shape),
            mode="drop") > 0)
        sc_new = jnp.where(has0[None, None],
                           jnp.maximum(fresh, quant.SCALE_FLOOR), sc)
        s_eff = sc_new[:, :, ii, c, ff]                      # [nb,na,T,khs]
        pool_new = pool.at[:, :, ii, c, ff, oo, :x.shape[-1]].set(
            quant.quantize(x, s_eff[..., None], kv_dtype), mode="drop")
        return pool_new, sc_new

    def _kv_body(self, state, k, v, inst, stripe, subf, off):
        khs = self.khs
        import jax.numpy as jnp
        from ..kernels import quant
        c = stripe[:, None] * khs + jnp.arange(khs, dtype=jnp.int32)
        ii, ff, oo = inst[:, None], subf[:, None], off[:, None]
        quantized = quant.is_quantized(self.dims.kv_dtype)
        state = dict(state)
        if self.cfg.is_mla:
            kp = state["kv_pool"]
            if quantized:
                state["kv_pool"], state["kv_scale"] = self._quantized_scatter(
                    kp, state["kv_scale"], k, ii, c, ff, oo, off)
            else:
                # the first dk lanes of the latent's whole-tile rows
                state["kv_pool"] = kp.at[:, :, ii, c, ff, oo,
                                         :k.shape[-1]].set(
                    k.astype(kp.dtype), mode="drop")
        else:
            kp, vp = state["k_pool"], state["v_pool"]
            if quantized:
                state["k_pool"], state["k_scale"] = self._quantized_scatter(
                    kp, state["k_scale"], k, ii, c, ff, oo, off)
                state["v_pool"], state["v_scale"] = self._quantized_scatter(
                    vp, state["v_scale"], v, ii, c, ff, oo, off)
            else:
                state["k_pool"] = kp.at[:, :, ii, c, ff, oo].set(
                    k.astype(kp.dtype), mode="drop")
                state["v_pool"] = vp.at[:, :, ii, c, ff, oo].set(
                    v.astype(vp.dtype), mode="drop")
        return state

    def scatter_kv(self, state: dict, k, v, coords: np.ndarray) -> dict:
        """k (and v for non-MLA): [nb, na, T, khs, kg*d] device arrays (the
        Hkv head axis reshaped to khs groups of kg heads); coords from
        ``prefill_coords`` (concatenated over the admitted batch).

        Quantized pools (dims.kv_dtype fp8/int8): the quantize step is FUSED
        into the scatter — full-precision prefill KV quantizes against the
        per-page scales derived in the same donated call (offset-0 rule), so
        unquantized KV never lands in the pool and the scale sidecar updates
        atomically with the pages it describes."""
        tb = self._bucket(k.shape[2])
        k = self._pad_to(k, 2, tb)
        v = self._pad_to(v, 2, tb)
        cs = self._pad_coords(coords, tb)
        if v is None:
            v = k                                     # unused by the MLA path
        return self._jit("kv", self._kv_body, state)(
            state, k, v, cs[0], cs[1], cs[2], cs[3])

    # -- SSM state ---------------------------------------------------------
    def _ssm_body(self, state, conv, h, inst, slot):
        din, ns = self.cfg.ssm_d_inner, self.cfg.ssm_state
        state = dict(state)
        for name, lo, hi in (("conv_x", 0, din),
                             ("conv_B", din, din + ns),
                             ("conv_C", din + ns, conv.shape[-1])):
            dst = state[name]
            state[name] = dst.at[:, :, inst, slot].set(
                conv[..., lo:hi].astype(dst.dtype), mode="drop")
        st = state["ssm_state"]
        state["ssm_state"] = st.at[:, :, inst, slot].set(
            h.astype(st.dtype), mode="drop")
        return state

    def scatter_ssm(self, state: dict, conv, h, inst_slot: np.ndarray) -> dict:
        """conv: [nb, n_ssm, R, cw-1, conv_dim], h: [nb, n_ssm, R, nh, hd, ns]
        device arrays; inst_slot int32 [2, R] (instance, slot) per request."""
        rb = self._bucket(conv.shape[2])
        conv = self._pad_to(conv, 2, rb)
        h = self._pad_to(h, 2, rb)
        cs = self._pad_coords(inst_slot, rb)
        return self._jit("ssm", self._ssm_body, state)(
            state, conv, h, cs[0], cs[1])

    # -- encoder-decoder (whisper) ------------------------------------------
    def _cross_body(self, state, k, v, inst, stripe, subf, off):
        khs = self.khs
        import jax.numpy as jnp
        c = stripe[:, None] * khs + jnp.arange(khs, dtype=jnp.int32)
        ii, ff, oo = inst[:, None], subf[:, None], off[:, None]
        state = dict(state)
        kp, vp = state["cross_k_pool"], state["cross_v_pool"]
        state["cross_k_pool"] = kp.at[:, ii, c, ff, oo].set(
            k.astype(kp.dtype), mode="drop")
        state["cross_v_pool"] = vp.at[:, ii, c, ff, oo].set(
            v.astype(vp.dtype), mode="drop")
        return state

    def scatter_cross_kv(self, state: dict, k, v, coords: np.ndarray) -> dict:
        """Whisper cross-attn KV (encoder states' projections) into the paged
        cross pools.  k/v: [L, T, khs, kg*d] device arrays; coords from
        ``prefill_coords``."""
        tb = self._bucket(k.shape[1])
        k, v = self._pad_to(k, 1, tb), self._pad_to(v, 1, tb)
        cs = self._pad_coords(coords, tb)
        return self._jit("cross", self._cross_body, state)(
            state, k, v, cs[0], cs[1], cs[2], cs[3])

    def _self_body(self, state, k, v, inst, slot, pos):
        import jax.numpy as jnp
        cc = jnp.arange(self.dims.tp, dtype=jnp.int32)[None, :]
        ii, ss, pp = inst[:, None], slot[:, None], pos[:, None]
        state = dict(state)
        sk, sv = state["self_k"], state["self_v"]
        state["self_k"] = sk.at[:, ii, cc, ss, pp].set(
            k.astype(sk.dtype), mode="drop")
        state["self_v"] = sv.at[:, ii, cc, ss, pp].set(
            v.astype(sv.dtype), mode="drop")
        return state

    def scatter_self_kv(self, state: dict, k, v, coords: np.ndarray) -> dict:
        """Whisper decoder-prefix self-attn KV into the per-slot contiguous
        caches.  k/v: [L, T, tp, kg*d] device arrays (head groups already
        tiled across page subgroups); coords int32 [3, T]
        (instance, slot, position) per prefix token."""
        tb = self._bucket(k.shape[1])
        k, v = self._pad_to(k, 1, tb), self._pad_to(v, 1, tb)
        cs = self._pad_coords(coords, tb)
        return self._jit("self", self._self_body, state)(
            state, k, v, cs[0], cs[1], cs[2])


class KVReshard:
    """Donated jitted collective moving RESIDENT KV between instances' pools.

    Mid-decode CP escalation / instance drain: gather the moved tokens' KV at
    their current (instance, frame, offset) pool coordinates, permute across
    the data axis (GSPMD lowers the cross-shard gather/scatter onto mesh
    collectives), and scatter into the newly allocated frames — one fused
    donated executable per padded token-count bucket, reusing
    ``PrefillScatter``'s jit/bucketing machinery and pinned output shardings
    so the pool buffers update in place (donation holds across the re-shard).

    Coordinates come from ``GlobalPageTable.move_pages`` ([3, T] int32
    (instance, frame, offset) per token, matching order).  All gathers read
    the PRE-move pools before any scatter writes, so a frame freed by one
    move and reallocated by another within the same batch stays correct.
    Coordinate uploads use EXPLICIT ``jax.device_put`` — the re-shard runs
    mid-steady-state, inside the engine's ``transfer_guard`` window.
    """

    def __init__(self, scatter: PrefillScatter, coord_sharding=None):
        self.sc = scatter
        self.coord_sharding = coord_sharding     # replicate over the mesh

    def _put(self, arr: np.ndarray):
        import jax
        return (jax.device_put(arr, self.coord_sharding)
                if self.coord_sharding is not None else jax.device_put(arr))

    def _body(self, state, src, dst):
        import jax.numpy as jnp
        from ..kernels import quant
        khs, ps = self.sc.khs, self.sc.ps
        hh = jnp.arange(khs, dtype=jnp.int32)
        si, sf, so = src[0][:, None], src[1], src[2][:, None]
        di, df, do = dst[0][:, None], dst[1], dst[2][:, None]
        c_s = (sf % ps)[:, None] * khs + hh
        c_d = (df % ps)[:, None] * khs + hh
        fs, fd = (sf // ps)[:, None], (df // ps)[:, None]
        kv_dtype = self.sc.dims.kv_dtype
        state = dict(state)
        if not quant.is_quantized(kv_dtype):
            keys = ("kv_pool",) if self.sc.cfg.is_mla else ("k_pool", "v_pool")
            for key in keys:
                p = state[key]
                # whole rows: a padded latent's zero pad lanes move with it
                vals = p[:, :, si, c_s, fs, so]      # [nb, na, T, khs, d]
                state[key] = p.at[:, :, di, c_d, fd, do].set(vals, mode="drop")
            return state
        # Quantized pools: scales travel with the re-shard.  Gather the moved
        # tokens and DEQUANT with their source page scales (pre-move values),
        # then REQUANT against the destination pages — fresh dst pages
        # (receiving an offset-0 token in this batch) get a new scale from
        # the moved tokens' scatter-max; partially-filled dst pages keep
        # their scale and the arrivals clip into it (offset-0 rule).  No
        # step ever mixes a value with another page's scale.
        pairs = ((("kv_pool", "kv_scale"),) if self.sc.cfg.is_mla
                 else (("k_pool", "k_scale"), ("v_pool", "v_scale")))
        for key, skey in pairs:
            p, sc = state[key], state[skey]
            vals = quant.dequantize(p[:, :, si, c_s, fs, so],
                                    sc[:, :, si, c_s, fs][..., None])
            tok = quant.amax_scale(vals, kv_dtype)           # [nb,na,T,khs]
            fresh = jnp.zeros_like(sc).at[:, :, di, c_d, fd].max(
                tok, mode="drop")
            has0 = (jnp.zeros(sc.shape[2:], jnp.int32).at[di, c_d, fd].max(
                jnp.broadcast_to((dst[2] == 0)[:, None].astype(jnp.int32),
                                 c_d.shape), mode="drop") > 0)
            sc_new = jnp.where(has0[None, None],
                               jnp.maximum(fresh, quant.SCALE_FLOOR), sc)
            s_eff = sc_new[:, :, di, c_d, fd]
            state[key] = p.at[:, :, di, c_d, fd, do].set(
                quant.quantize(vals, s_eff[..., None], kv_dtype), mode="drop")
            state[skey] = sc_new
        return state

    def __call__(self, state: dict, src: np.ndarray, dst: np.ndarray) -> dict:
        """Apply one batched re-shard (possibly many requests' moves)."""
        assert src.shape == dst.shape and src.shape[0] == 3, (src.shape,
                                                              dst.shape)
        T = src.shape[1]
        if T == 0:
            return state
        tb = self.sc._bucket(T)
        sp = np.zeros((3, tb - T), np.int32)         # src pad reads coord 0
        dp = np.zeros((3, tb - T), np.int32)
        dp[0] = self.sc.I                            # dst pad rows drop
        s = self._put(np.concatenate([src.astype(np.int32), sp], axis=1))
        d = self._put(np.concatenate([dst.astype(np.int32), dp], axis=1))
        return self.sc._jit("reshard", self._body, state)(state, s, d)


def load_prefill_cross_kv(cfg: ModelConfig, cluster: ClusterState,
                          dims: DecodeDims, state_np: dict, rid: int,
                          cross_layers) -> None:
    """Whisper: write per-decoder-layer cross-attn KV (the encoder states'
    projections) into the paged cross pools per the DCP placement.

    cross_layers: per decoder layer, (k [S_enc, Hkv, hd], v [S_enc, Hkv, hd]).
    """
    page = dims.page
    pt = cluster.page_table
    ranges = shard_ranges(cluster, rid)
    _, khs, ps = attn_tp_geometry(cfg, dims.tp)
    kg = kv_group_size(cfg, dims.tp)
    for l, (k, v) in enumerate(cross_layers):
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        for s, start, t in ranges:
            frames = pt.shard_frames(rid, s)
            for j in range(t):
                f, o = frames[j // page], j % page
                for h in range(khs):
                    c = (f % ps) * khs + h
                    grp = slice(h * kg, (h + 1) * kg)
                    state_np["cross_k_pool"][l, s, c, f // ps, o] = \
                        k[start + j, grp].reshape(-1)
                    state_np["cross_v_pool"][l, s, c, f // ps, o] = \
                        v[start + j, grp].reshape(-1)


def load_prefill_self_kv(cfg: ModelConfig, dims: DecodeDims, state_np: dict,
                         instance: int, slot: int, self_layers) -> None:
    """Whisper: decoder-prefix self-attn KV into the per-slot contiguous cache.

    self_layers: per decoder layer, (k [T0, Hkv, hd], v [T0, Hkv, hd]).
    """
    _, khs, ps = attn_tp_geometry(cfg, dims.tp)
    kg = kv_group_size(cfg, dims.tp)
    for l, (k, v) in enumerate(self_layers):
        t0 = k.shape[0]
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        for c in range(khs * ps):
            grp = slice((c % khs) * kg, (c % khs + 1) * kg)
            state_np["self_k"][l, instance, c, slot, :t0] = \
                k[:, grp].reshape(t0, -1)
            state_np["self_v"][l, instance, c, slot, :t0] = \
                v[:, grp].reshape(t0, -1)
