"""Unified language model: pattern-scanned decoder (+ optional encoder /
modality memory), with train forward, loss, prefill and one-token decode.

Layers are stored STACKED (leading dim = n_groups) and executed with
jax.lax.scan so compile time is independent of depth; remat policy wraps
the per-group apply.  Device ops carry the names of ``layers.SCOPES``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from ..obs import metrics
from .blocks import REGISTRY
from .layers import dtype_of, embed_init, pdtype_of, rmsnorm, scope

PREFILL_PATHS = ("sequence", "token")


def _prefill_lowerings(path: str) -> metrics.Counter:
    return metrics.counter("repro_jax_prefill_lowerings_total",
                           "prefill programs traced, by path: one "
                           "sequence-parallel pass or a loop over tokens",
                           path=path)


for _path in PREFILL_PATHS:
    _prefill_lowerings(_path)


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _group_init(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, len(cfg.pattern))
        return {f"b{i}": REGISTRY[kind].init(keys[i], cfg)
                for i, kind in enumerate(cfg.pattern)}

    def init(self, key) -> Dict:
        cfg = self.cfg
        k_embed, k_groups, k_head, k_enc, k_mtp, k_pre = \
            jax.random.split(key, 6)
        pd = pdtype_of(cfg)
        params: Dict[str, Any] = {
            "tok_embed": embed_init(k_embed, cfg.vocab, cfg.d_model, pd),
            "final_norm": jnp.ones((cfg.d_model,), pd),
        }
        group_keys = jax.random.split(k_groups, cfg.n_groups)
        params["groups"] = jax.vmap(self._group_init)(group_keys)
        if cfg.first_dense > 0:
            pre_keys = jax.random.split(k_pre, cfg.first_dense)
            params["prefix"] = jax.vmap(
                lambda k: REGISTRY[cfg.prefix_kind].init(
                    k, self._prefix_cfg()))(pre_keys)
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab)) * 0.02).astype(pd)
        if cfg.enc_layers > 0:
            enc_keys = jax.random.split(k_enc, cfg.enc_layers)
            params["encoder"] = {
                "blocks": jax.vmap(
                    lambda k: REGISTRY["enc_attn"].init(k, cfg))(enc_keys),
                "final_norm": jnp.ones((cfg.d_model,), pd),
            }
        if cfg.mtp_depth > 0:
            km1, km2 = jax.random.split(k_mtp)
            params["mtp"] = {
                "proj": (jax.random.normal(km1, (2 * cfg.d_model,
                                                 cfg.d_model))
                         * (2 * cfg.d_model) ** -0.5).astype(pd),
                "block": REGISTRY["attn"].init(
                    km2, cfg.replace(pattern=("attn",))),
                "norm_h": jnp.ones((cfg.d_model,), pd),
                "norm_e": jnp.ones((cfg.d_model,), pd),
            }
        return params

    def _prefix_cfg(self) -> ModelConfig:
        """The config the ``first_dense`` leading layers run under."""
        return self.cfg.replace(pattern=(self.cfg.prefix_kind,))

    # -------------------------------------------------------------- forward
    @scope("embed")
    def _embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["tok_embed"], tokens, axis=0).astype(
            dtype_of(cfg))
        return constrain(x, ("batch", "seq", "embed"))

    def _logits(self, params, x):
        cfg = self.cfg
        head = (params["tok_embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(dtype_of(cfg))
        logits = (x @ head) * cfg.logit_scale
        return constrain(logits, ("batch", "seq", "vocab"))

    @scope("head")
    def _head(self, params, x):
        """Final norm and logits."""
        x = rmsnorm(x, params["final_norm"], self.cfg.norm_eps)
        return self._logits(params, x)

    def _group_apply(self, gparams, x, memory):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.pattern):
            x, a = REGISTRY[kind].apply(gparams[f"b{i}"], x, cfg,
                                        memory=memory)
            aux = aux + a
        return x, aux

    @scope("layers")
    def _run_groups(self, params, x, memory):
        cfg = self.cfg
        apply = self._group_apply
        if cfg.remat != "none":
            policy = (jax.checkpoint_policies.nothing_saveable
                      if cfg.remat == "full"
                      else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            apply = jax.checkpoint(apply, policy=policy,
                                   static_argnums=())
        if cfg.scan_layers:
            def body(carry, gparams):
                h, aux = carry
                h, a = apply(gparams, h, memory)
                return (h, aux + a), None
            (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                       params["groups"])
        else:
            aux = jnp.zeros((), jnp.float32)
            for g in range(cfg.n_groups):
                gp = jax.tree.map(lambda a: a[g], params["groups"])
                x, a = apply(gp, x, memory)
                aux = aux + a
        return x, aux

    @scope("layers")
    def _run_prefix(self, params, x):
        cfg = self.cfg
        if cfg.first_dense == 0:
            return x
        dense_cfg = self._prefix_cfg()
        block = REGISTRY[cfg.prefix_kind]

        if cfg.scan_layers:
            def body(h, bparams):
                h, _ = block.apply(bparams, h, dense_cfg)
                return h, None
            x, _ = jax.lax.scan(body, x, params["prefix"])
        else:
            for i in range(cfg.first_dense):
                bp = jax.tree.map(lambda a: a[i], params["prefix"])
                x, _ = block.apply(bp, x, dense_cfg)
        return x

    def _encode(self, params, frame_embeds):
        cfg = self.cfg
        x = frame_embeds.astype(dtype_of(cfg))

        with scope("layers"):
            if cfg.scan_layers:
                def body(h, bparams):
                    h, _ = REGISTRY["enc_attn"].apply(bparams, h, cfg)
                    return h, None
                x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
            else:
                for i in range(cfg.enc_layers):
                    bp = jax.tree.map(lambda a: a[i],
                                      params["encoder"]["blocks"])
                    x, _ = REGISTRY["enc_attn"].apply(bp, x, cfg)
        return rmsnorm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    def forward(self, params, tokens, *, memory_embeds=None
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens: (B, S) -> (logits (B, S, V), aux_loss scalar).

        memory_embeds: (B, M, D) stub frontend output (audio frames /
        image patches) for audio/vlm families; encoder runs here for
        enc-dec models.
        """
        cfg = self.cfg
        memory = None
        if cfg.enc_layers > 0:
            assert memory_embeds is not None, "enc-dec model needs frames"
            memory = self._encode(params, memory_embeds)
        elif memory_embeds is not None:
            memory = memory_embeds.astype(dtype_of(cfg))

        x = self._embed(params, tokens)
        x = self._run_prefix(params, x)
        x, aux = self._run_groups(params, x, memory)
        return self._head(params, x), aux

    # ----------------------------------------------------------------- loss
    def loss_fn(self, params, batch: Dict) -> Tuple[jax.Array, Dict]:
        """batch: tokens (B,S), labels (B,S) (-100 = ignore), optional
        memory_embeds."""
        cfg = self.cfg
        trunk = None
        if cfg.mtp_depth > 0 and cfg.mtp_share_trunk:
            # §Perf: compute the trunk ONCE; head + MTP both read it
            memory = None
            if batch.get("memory_embeds") is not None:
                memory = batch["memory_embeds"].astype(dtype_of(cfg))
            x = self._embed(params, batch["tokens"])
            x = self._run_prefix(params, x)
            trunk, aux = self._run_groups(params, x, memory)
            logits = self._head(params, trunk)
        else:
            logits, aux = self.forward(
                params, batch["tokens"],
                memory_embeds=batch.get("memory_embeds"))
        labels = batch["labels"]
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(valid), 1)
        xent = jnp.sum(jnp.where(valid, nll, 0.0)) / denom
        metrics = {"xent": xent, "aux": aux}
        loss = xent + aux

        if cfg.mtp_depth > 0:
            loss = loss + 0.3 * self._mtp_loss(params, batch, metrics,
                                               trunk=trunk)
        return loss, metrics

    def _mtp_loss(self, params, batch, metrics, trunk=None) -> jax.Array:
        """DeepSeek-V3 multi-token prediction: predict t+2 from a fused
        (h_t, emb_{t+1}) stream through one extra block."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        if trunk is None:
            # hidden states (pre-head) for the main stream
            x = self._embed(params, tokens)
            x = self._run_prefix(params, x)
            x, _ = self._run_groups(params, x, None)
        else:
            x = trunk
        h = rmsnorm(x, params["mtp"]["norm_h"], cfg.norm_eps)
        e_next = rmsnorm(self._embed(params, jnp.roll(tokens, -1, axis=1)),
                         params["mtp"]["norm_e"], cfg.norm_eps)
        fused = jnp.concatenate([h, e_next], axis=-1) \
            @ params["mtp"]["proj"].astype(dtype_of(cfg))
        fused, _ = REGISTRY["attn"].apply(params["mtp"]["block"], fused,
                                          cfg.replace(pattern=("attn",)))
        with scope("head"):
            logits = self._logits(params, fused)
        mtp_labels = jnp.roll(labels, -1, axis=1)
        valid = mtp_labels >= 0
        valid = valid.at[:, -2:].set(False)
        safe = jnp.where(valid, mtp_labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        mtp = jnp.sum(jnp.where(valid, nll, 0.0)) \
            / jnp.maximum(jnp.sum(valid), 1)
        metrics["mtp"] = mtp
        return mtp

    # --------------------------------------------------------------- decode
    def init_cache(self, batch: int, max_len: int, *, abstract: bool = False
                   ) -> Dict:
        cfg = self.cfg

        def group_cache():
            return {f"b{i}": REGISTRY[kind].cache(cfg, batch, max_len)
                    for i, kind in enumerate(cfg.pattern)}

        proto = jax.eval_shape(group_cache)
        stack = (lambda a: jax.ShapeDtypeStruct((cfg.n_groups,) + a.shape,
                                                a.dtype)) if abstract else \
                (lambda a: jnp.zeros((cfg.n_groups,) + a.shape, a.dtype))
        cache: Dict[str, Any] = {"groups": jax.tree.map(stack, proto)}
        if cfg.first_dense > 0:
            pre = jax.eval_shape(
                lambda: REGISTRY[cfg.prefix_kind].cache(cfg, batch, max_len))
            stack_p = (lambda a: jax.ShapeDtypeStruct(
                (cfg.first_dense,) + a.shape, a.dtype)) if abstract else \
                (lambda a: jnp.zeros((cfg.first_dense,) + a.shape, a.dtype))
            cache["prefix"] = jax.tree.map(stack_p, pre)
        return cache

    def decode_step(self, params, cache: Dict, tokens, pos, *,
                    memory_embeds=None) -> Tuple[jax.Array, Dict]:
        """tokens: (B, 1); pos: scalar int32 -> (logits (B, V), new cache)."""
        cfg = self.cfg
        memory = None
        if cfg.enc_layers > 0:
            assert memory_embeds is not None
            memory = self._encode(params, memory_embeds)
        elif memory_embeds is not None:
            memory = memory_embeds.astype(dtype_of(cfg))

        x = self._embed(params, tokens)
        new_cache: Dict[str, Any] = {}

        if cfg.first_dense > 0:
            dense_cfg = self._prefix_cfg()

            def pre_body(h, inp):
                bp, bc = inp
                return REGISTRY[cfg.prefix_kind].decode(bp, h, bc, pos,
                                                        dense_cfg)

            with scope("layers"):
                x, new_cache["prefix"] = self._decode_layers(
                    pre_body, x, params["prefix"], cache["prefix"],
                    cfg.first_dense)

        def body(h, inp):
            gp, gc = inp
            ncs = {}
            for i, kind in enumerate(cfg.pattern):
                h, nc = REGISTRY[kind].decode(gp[f"b{i}"], h, gc[f"b{i}"],
                                              pos, cfg, memory=memory)
                ncs[f"b{i}"] = nc
            return h, ncs

        with scope("layers"):
            x, new_cache["groups"] = self._decode_layers(
                body, x, params["groups"], cache["groups"], cfg.n_groups)
        logits = self._head(params, x)[:, 0, :]
        return logits, new_cache

    def _decode_layers(self, body, x, params, cache, n: int):
        """``body`` over the stacked layers' params and cache: scanned, or
        unrolled; returns x and the new stacked cache."""
        if self.cfg.scan_layers:
            return jax.lax.scan(body, x, (params, cache))
        ncs = []
        for i in range(n):
            x, nc = body(x, jax.tree.map(lambda a: a[i], (params, cache)))
            ncs.append(nc)
        return x, jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)

    def prefill(self, params, tokens, cache: Dict, *, memory_embeds=None):
        """tokens: (B, S) prompt; cache from ``init_cache`` -> (logits of
        the last token (B, V) in the config's dtype, the cache filled
        through position S-1).

        When every block can take the whole prompt at once (each kind's
        ``BlockDef.prefill``: the attention, latent-attention, expert and
        SSM blocks) and there is no memory, one sequence-parallel pass:
        each layer runs over all S positions as ``forward`` does and writes
        its cache in one store (S keys and values or latents; an SSM
        block's conv window and its state after the chunked scan), and
        only the last position goes through the head.  Other models
        (RG-LRU state, memory) run ``decode_step`` over the prompt one
        token at a time.
        Each trace counts its path in
        ``repro_jax_prefill_lowerings_total{path}``."""
        cfg = self.cfg
        kinds = cfg.pattern + ((cfg.prefix_kind,) if cfg.first_dense > 0
                               else ())
        sequence = (memory_embeds is None and cfg.enc_layers == 0
                    and all(REGISTRY[k].prefill for k in kinds))
        _prefill_lowerings("sequence" if sequence else "token").inc()
        if sequence:
            return self._prefill_sequence(params, tokens, cache)
        return self._prefill_tokens(params, tokens, cache,
                                    memory_embeds=memory_embeds)

    def _prefill_sequence(self, params, tokens, cache: Dict):
        cfg = self.cfg
        x = self._embed(params, tokens)
        new_cache: Dict[str, Any] = {}

        if cfg.first_dense > 0:
            dense_cfg = self._prefix_cfg()

            def pre_body(h, inp):
                bp, bc = inp
                return REGISTRY[cfg.prefix_kind].prefill(bp, h, bc,
                                                         dense_cfg)

            with scope("layers"):
                x, new_cache["prefix"] = self._decode_layers(
                    pre_body, x, params["prefix"], cache["prefix"],
                    cfg.first_dense)

        def body(h, inp):
            gp, gc = inp
            ncs = {}
            for i, kind in enumerate(cfg.pattern):
                h, ncs[f"b{i}"] = REGISTRY[kind].prefill(
                    gp[f"b{i}"], h, gc[f"b{i}"], cfg)
            return h, ncs

        with scope("layers"):
            x, new_cache["groups"] = self._decode_layers(
                body, x, params["groups"], cache["groups"], cfg.n_groups)
        logits = self._head(params, x[:, -1:, :])[:, 0, :]
        return logits, new_cache

    def _prefill_tokens(self, params, tokens, cache: Dict, *,
                        memory_embeds=None):
        """The prompt through ``decode_step``, one token at a time."""
        s = tokens.shape[1]

        def body(carry, t):
            cache, last = carry
            with scope("embed"):
                token = tokens[:, t][:, None]
            logits, cache = self.decode_step(
                params, cache, token, t, memory_embeds=memory_embeds)
            return (cache, logits), None

        # the carry's logits take decode_step's dtype (the config's)
        (cache, logits), _ = jax.lax.scan(
            body, (cache, jnp.zeros((tokens.shape[0], self.cfg.vocab),
                                    dtype_of(self.cfg))),
            jnp.arange(s))
        return logits, cache

    # ----------------------------------------------------------- analytics
    def param_count(self, params) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(params))


def build(cfg: ModelConfig) -> LanguageModel:
    return LanguageModel(cfg)
