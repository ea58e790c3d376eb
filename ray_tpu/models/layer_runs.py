"""What the models that walk RUNS of like layers share (``JambaModel``,
``Lfm2Model``): a hybrid's layers are of a few KINDS, each kind a
parameter tree that stacks its layers, and every program walks the runs
of consecutive layers of one kind (``self.runs``: ``(kind, first index in
the kind's stack, layers)``), a run of several layers as ONE ``lax.scan``
over its indices. What a program keeps a layer (the attention layers' K/V
rows, a recurrent state, counters) is a ``store``: a WHOLE stack a name,
carried through every run and written at the layer's index in place.

``LayerRuns`` is a mixin before a ``LlamaModel`` (whose ``_qkv``,
``_attend_rows``, ``_attend_pages``, ``kv_row_shapes`` and
``paged_decode_impl`` it calls); the model brings ``cfg.attn_layers``,
``init_state``, its own mixer for the other kind and the body of a layer.
The ATTENTION layers' side of the three serving programs is here once: the
slot cache of ``forward_step``, the gathered prefix of
``prefill_with_prefix`` and the pages of ``decode_step_paged_counted``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

Params = Dict


class LayerRuns:
    # the scope around the paged kernel's call and everything beside it
    PAGED_ATTENTION_SCOPE = "attention"

    @staticmethod
    def _at(stack, j):
        return jax.lax.dynamic_index_in_dim(stack, j, 0, keepdims=False)

    @staticmethod
    def _put(stack, j, value):
        return jax.lax.dynamic_update_index_in_dim(
            stack, value.astype(stack.dtype), j, 0)

    def _over_runs(self, body_of, carry):
        """``body_of(kind)(carry, j) -> (carry, None)``, layer ``j`` of the
        kind's stack: a run of one layer is called, a longer one scanned."""
        for kind, first, count in self.runs:
            if count == 1:
                carry, _ = body_of(kind)(carry, jnp.int32(first))
            else:
                carry, _ = jax.lax.scan(
                    body_of(kind), carry,
                    first + jnp.arange(count, dtype=jnp.int32))
        return carry

    # -- the cache's K/V rows ----------------------------------------------------
    def _kv_zeros(self, *leading: int) -> Params:
        return {name: jnp.zeros((self.cfg.attn_layers,) + leading + row,
                                self.kv_dtype)
                for name, row in zip(("k", "v"), self.kv_row_shapes())}

    def init_kv_cache(self, batch: int, max_seq: int) -> Params:
        """Slot-major cache: k/v [La, B, S, rows of K/V heads] of the
        attention layers and the recurrent state a row."""
        return {**self._kv_zeros(batch, max_seq), **self.init_state(batch)}

    def init_kv_pool(self, num_blocks: int, block_size: int,
                     slots: int = 0) -> Params:
        """The attention layers' block pool, k/v [La, num_blocks, bs, rows
        of K/V heads], and with ``slots`` the recurrent state a SLOT
        beside it: ONE tree, which the decode step takes and hands back
        whole."""
        pool = self._kv_zeros(num_blocks, block_size)
        return {**pool, **self.init_state(slots)} if slots else pool

    # -- the attention layers' mixer, a program --------------------------------
    def _attention_mixer(self, h, layer: Params, positions, attend):
        """h [B, T, D] (normed) -> (out [B, T, D], ``attend``'s second
        result: the calling program's store with this layer's rows in)."""
        dt = self.cfg.dtype
        with jax.named_scope("attention"):
            q, k, v = self._qkv(h, layer, positions, None,
                                lambda a, *names: a)
        o, kv = attend(q, k, v)
        with jax.named_scope("attention"):
            return jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt)), kv

    def _rows_attention(self, attend_rows, positions):
        """A walk's attention mixer for a prefill: ``attend_rows(q, k_new,
        v_new, j, store) -> (o, store)``."""
        def mixer(h, layer, j, store):
            return self._attention_mixer(
                h, layer, positions,
                lambda q, k, v: attend_rows(q, k, v, j, store))
        return mixer

    def _slot_attention(self, cache: Params, offsets, T: int):
        """``forward_step``'s: this call's T rows written into the slot
        cache ``store["k"]`` / ``["v"]`` [La, B, S, ...] behind each row's
        ``offsets`` and attended over the whole of it."""
        S = cache["k"].shape[2] if "k" in cache else 0
        q_pos = offsets[:, None] + jnp.arange(T)[None, :]
        batch_idx = jnp.arange(offsets.shape[0])[:, None]

        def attend_rows(q, k_new, v_new, j, store):
            with jax.named_scope("kv_update"):
                k_all = self._at(store["k"], j).at[batch_idx, q_pos].set(
                    k_new)
                v_all = self._at(store["v"], j).at[batch_idx, q_pos].set(
                    v_new)
            with jax.named_scope("attention"):
                o = self._attend_rows(q, k_all, v_all, None, q_pos,
                                      jnp.arange(S))
            return o, dict(store, k=self._put(store["k"], j, k_all),
                           v=self._put(store["v"], j, v_all))

        return self._rows_attention(attend_rows, q_pos)

    def _prefix_attention(self, prefix_k, prefix_v, prefix_len, Tb: int):
        """``prefill_with_prefix``'s: a chunk's Tb rows attend over the
        gathered prefix [La, N, Pmax, ...] (its first ``prefix_len`` rows
        a row) and themselves; ``store["k"]`` / ``["v"]`` [La, N, Tb, ...]
        take the chunk's own rows."""
        Pmax = prefix_k.shape[2]
        pos_q = prefix_len[:, None] + jnp.arange(Tb)[None, :]
        far = jnp.int32(2 ** 30)
        pos_prefix = jnp.where(
            jnp.arange(Pmax)[None, :] < prefix_len[:, None],
            jnp.arange(Pmax)[None, :], far)
        pos_k = jnp.concatenate([pos_prefix, pos_q], axis=1)

        def attend_rows(q, k_new, v_new, j, store):
            with jax.named_scope("attention"):
                o = self._attend_rows(
                    q, jnp.concatenate([self._at(prefix_k, j).astype(
                        k_new.dtype), k_new], axis=1),
                    jnp.concatenate([self._at(prefix_v, j).astype(
                        v_new.dtype), v_new], axis=1),
                    None, pos_q, pos_k)
            return o, dict(store, k=self._put(store["k"], j, k_new),
                           v=self._put(store["v"], j, v_new))

        return self._rows_attention(attend_rows, pos_q)

    def _paged_attention(self, pool: Params, block_tables, offsets,
                         run: int):
        """``decode_step_paged_counted``'s. -> (the pool's K/V as ONE stack
        a name, ``[La*NB, bs, ...]`` with layer ``j``'s pages from
        ``j*NB`` on, for the store (nothing where the tree holds no
        pages); the walk's attention mixer: the step's row written at its
        block, the kernel or its reference over the layer's window)."""
        if "k" not in pool:
            return {}, None
        impl = self.paged_decode_impl()
        q_pos = offsets[:, None]
        lengths = offsets + 1
        La, NB, bs = pool["k"].shape[:3]
        stacks = {name: pool[name].reshape((La * NB,) + pool[name].shape[2:])
                  for name in ("k", "v")}
        dest_block = jnp.take_along_axis(
            block_tables, (offsets // bs)[:, None], axis=-1)[:, 0]
        dest_off = offsets % bs

        def attn_mixer(h, layer, j, store):
            def attend(q, k_new, v_new):
                with jax.named_scope("kv_update"):
                    k_all = store["k"].at[j * NB + dest_block, dest_off].set(
                        k_new[:, 0])
                    v_all = store["v"].at[j * NB + dest_block, dest_off].set(
                        v_new[:, 0])
                with jax.named_scope(self.PAGED_ATTENTION_SCOPE):
                    o = self._attend_pages(
                        q[:, 0], k_all, v_all, None, block_tables, lengths,
                        impl=impl, starts=None, first_block=j * NB,
                        num_blocks=NB, run=run)
                return o[:, None], dict(store, k=k_all, v=v_all)

            return self._attention_mixer(h, layer, q_pos, attend)

        return stacks, attn_mixer

    @staticmethod
    def _pages_back(pool: Params, store: Params) -> Params:
        """The pool with the store's stacks of pages in their own shape."""
        return dict(pool, **{name: store[name].reshape(pool[name].shape)
                             for name in ("k", "v") if name in store})
