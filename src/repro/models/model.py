"""Unified language model: pattern-scanned decoder (+ optional encoder /
modality memory), with train forward, loss, prefill and one-token decode.

Layers are stored STACKED (leading dim = layer count) and executed with
jax.lax.scan so compile time is independent of depth; remat policy wraps
the per-group apply.  Each stack (``Stack``: the leading dense
``prefix``, the ``pattern`` ``groups``, the encoder) runs through one
walk, ``LanguageModel._walk``.  Device ops carry the names of
``layers.SCOPES``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

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


class Stack(NamedTuple):
    """``count`` layers stacked on a leading axis, each the block ``kinds``
    in order, run under ``cfg``.  A ``grouped`` layer (the pattern group)
    keys each block's params and cache ``b{i}``; otherwise a layer is its
    one block's own.  ``remat`` wraps each layer's ``forward`` in
    ``cfg.remat``'s checkpoint policy."""
    kinds: Tuple[str, ...]
    cfg: ModelConfig
    count: int
    grouped: bool = False
    remat: bool = False

    def blocks(self):
        return [(f"b{i}", REGISTRY[k]) for i, k in enumerate(self.kinds)]

    def part(self, layer, key: str):
        """One block's part of a layer's params or cache."""
        return layer[key] if self.grouped else layer

    def layer(self, parts: Dict[str, Any]):
        """A layer's tree from its blocks' parts."""
        return parts if self.grouped else parts["b0"]

    def init(self, key):
        """One layer's params."""
        keys = (jax.random.split(key, len(self.kinds)) if self.grouped
                else (key,))
        return self.layer({b: block.init(k, self.cfg) for (b, block), k
                           in zip(self.blocks(), keys)})

    def cache(self, batch: int, max_len: int):
        """One layer's decode cache."""
        return self.layer({b: block.cache(self.cfg, batch, max_len)
                           for b, block in self.blocks()})


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def _stacks(self) -> Dict[str, Stack]:
        """The decoder's stacks by params and cache key, in the order they
        run: the ``first_dense`` leading layers of ``prefix_kind`` (no
        remat), then the ``n_groups`` pattern groups."""
        cfg = self.cfg
        stacks = {}
        if cfg.first_dense > 0:
            stacks["prefix"] = Stack(
                (cfg.prefix_kind,), cfg.replace(pattern=(cfg.prefix_kind,)),
                cfg.first_dense)
        stacks["groups"] = Stack(cfg.pattern, cfg, cfg.n_groups,
                                 grouped=True, remat=True)
        return stacks

    def _encoder(self) -> Stack:
        return Stack(("enc_attn",), self.cfg, self.cfg.enc_layers)

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        cfg = self.cfg
        k_embed, k_groups, k_head, k_enc, k_mtp, k_pre = \
            jax.random.split(key, 6)
        pd = pdtype_of(cfg)

        def stacked(stack, key):
            return jax.vmap(stack.init)(jax.random.split(key, stack.count))

        params: Dict[str, Any] = {
            "tok_embed": embed_init(k_embed, cfg.vocab, cfg.d_model, pd),
            "final_norm": jnp.ones((cfg.d_model,), pd),
        }
        stacks = self._stacks()
        params["groups"] = stacked(stacks["groups"], k_groups)
        if "prefix" in stacks:
            params["prefix"] = stacked(stacks["prefix"], k_pre)
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab)) * 0.02).astype(pd)
        if cfg.enc_layers > 0:
            params["encoder"] = {
                "blocks": stacked(self._encoder(), k_enc),
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

    # ----------------------------------------------------------- layer walk
    def _walk(self, body: Callable, carry, xs):
        """``body(carry, one layer's slice of xs) -> (carry, y)`` over the
        layers stacked in ``xs``: scanned, or unrolled when not
        ``cfg.scan_layers``; returns the carry and the ys stacked."""
        if self.cfg.scan_layers:
            return jax.lax.scan(body, carry, xs)
        ys = []
        for i in range(jax.tree.leaves(xs)[0].shape[0]):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    def _run(self, stack: Stack, params, x, memory=None):
        """x through the stack's layers as ``forward`` runs them -> (x, the
        blocks' aux loss summed)."""
        cfg = self.cfg

        def layer(lp, h, memory):
            aux = jnp.zeros((), jnp.float32)
            for b, block in stack.blocks():
                h, a = block.apply(stack.part(lp, b), h, stack.cfg,
                                   memory=memory)
                aux = aux + a
            return h, aux

        if stack.remat and cfg.remat != "none":
            policy = (jax.checkpoint_policies.nothing_saveable
                      if cfg.remat == "full"
                      else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            layer = jax.checkpoint(layer, policy=policy, static_argnums=())

        def body(carry, lp):
            h, aux = carry
            h, a = layer(lp, h, memory)
            return (h, aux + a), None

        with scope("layers"):
            (x, aux), _ = self._walk(body, (x, jnp.zeros((), jnp.float32)),
                                     params)
        return x, aux

    def _step(self, stack: Stack, params, cache, x, step: Callable):
        """x through the stack's layers with their cache, ``step(block, p,
        x, c, cfg) -> (x, new c)`` for each block -> (x, the new stacked
        cache)."""
        def body(h, inp):
            lp, lc = inp
            new = {}
            for b, block in stack.blocks():
                h, new[b] = step(block, stack.part(lp, b), h,
                                 stack.part(lc, b), stack.cfg)
            return h, stack.layer(new)

        with scope("layers"):
            return self._walk(body, x, (params, cache))

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

    def _memory(self, params, memory_embeds):
        """The memory the cross-attention blocks read: the encoder's output
        for enc-dec models, else the stub frontend's embeddings (or
        None)."""
        cfg = self.cfg
        if cfg.enc_layers > 0:
            assert memory_embeds is not None, "enc-dec model needs frames"
            x = memory_embeds.astype(dtype_of(cfg))
            x, _ = self._run(self._encoder(), params["encoder"]["blocks"], x)
            return rmsnorm(x, params["encoder"]["final_norm"], cfg.norm_eps)
        if memory_embeds is not None:
            return memory_embeds.astype(dtype_of(cfg))
        return None

    def _trunk(self, params, tokens, memory_embeds=None):
        """tokens through the decoder's layers -> (hidden states before the
        head, aux loss of the pattern groups)."""
        memory = self._memory(params, memory_embeds)
        x = self._embed(params, tokens)
        stacks = self._stacks()
        if "prefix" in stacks:
            x, _ = self._run(stacks["prefix"], params["prefix"], x)
        return self._run(stacks["groups"], params["groups"], x, memory)

    def forward(self, params, tokens, *, memory_embeds=None
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens: (B, S) -> (logits (B, S, V), aux_loss scalar).

        memory_embeds: (B, M, D) stub frontend output (audio frames /
        image patches) for audio/vlm families; encoder runs here for
        enc-dec models.
        """
        x, aux = self._trunk(params, tokens, memory_embeds)
        return self._head(params, x), aux

    # ----------------------------------------------------------------- loss
    def loss_fn(self, params, batch: Dict) -> Tuple[jax.Array, Dict]:
        """batch: tokens (B,S), labels (B,S) (-100 = ignore), optional
        memory_embeds."""
        cfg = self.cfg
        trunk, aux = self._trunk(params, batch["tokens"],
                                 batch.get("memory_embeds"))
        logits = self._head(params, trunk)
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
            loss = loss + 0.3 * self._mtp_loss(params, batch, metrics, trunk)
        return loss, metrics

    def _mtp_loss(self, params, batch, metrics, trunk) -> jax.Array:
        """DeepSeek-V3 multi-token prediction: predict t+2 from a fused
        (h_t, emb_{t+1}) stream through one extra block; h_t is the
        main stream's ``trunk``."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        h = rmsnorm(trunk, params["mtp"]["norm_h"], cfg.norm_eps)
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
        cache: Dict[str, Any] = {}
        for name, stack in self._stacks().items():
            proto = jax.eval_shape(lambda: stack.cache(batch, max_len))
            n = stack.count
            cache[name] = jax.tree.map(
                (lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype))
                if abstract else
                (lambda a: jnp.zeros((n,) + a.shape, a.dtype)), proto)
        return cache

    def decode_step(self, params, cache: Dict, tokens, pos, *,
                    memory_embeds=None) -> Tuple[jax.Array, Dict]:
        """tokens: (B, 1); pos: scalar int32 -> (logits (B, V), new cache)."""
        memory = self._memory(params, memory_embeds)
        x = self._embed(params, tokens)
        new_cache: Dict[str, Any] = {}
        for name, stack in self._stacks().items():
            x, new_cache[name] = self._step(
                stack, params[name], cache[name], x,
                lambda block, p, h, c, cfg: block.decode(
                    p, h, c, pos, cfg, memory=memory))
        logits = self._head(params, x)[:, 0, :]
        return logits, new_cache

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
        sequence = (memory_embeds is None and cfg.enc_layers == 0
                    and all(block.prefill for stack in self._stacks().values()
                            for _, block in stack.blocks()))
        _prefill_lowerings("sequence" if sequence else "token").inc()
        if sequence:
            return self._prefill_sequence(params, tokens, cache)
        return self._prefill_tokens(params, tokens, cache,
                                    memory_embeds=memory_embeds)

    def _prefill_sequence(self, params, tokens, cache: Dict):
        x = self._embed(params, tokens)
        new_cache: Dict[str, Any] = {}
        for name, stack in self._stacks().items():
            x, new_cache[name] = self._step(
                stack, params[name], cache[name], x,
                lambda block, p, h, c, cfg: block.prefill(p, h, c, cfg))
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
