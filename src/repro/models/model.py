"""Unified model API used by the trainer, server, dry-run, and tests.

``LM`` is a thin, stateless wrapper over the pure functions in
``transformer.py``; it owns only the config.  All heavy state (params,
caches) flows through arguments so every method jits/lowers cleanly.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import kvcache, transformer
from repro.models.spec import abstract_params, count_params, init_params, logical_axes

__all__ = ["LM"]


class LM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.spec = transformer.model_spec(cfg)

    # ----------------------------------------------------------- params

    def init(self, seed: int = 0):
        dtype = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        return init_params(self.spec, seed=seed, dtype=dtype)

    def abstract_params(self):
        dtype = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        return abstract_params(self.spec, dtype=dtype)

    def param_axes(self):
        return logical_axes(self.spec)

    def num_params(self) -> int:
        return count_params(self.spec)

    # ----------------------------------------------------------- compute

    def forward(self, params, tokens):
        return transformer.forward(params, tokens, self.cfg)

    def loss(self, params, batch):
        return transformer.loss_fn(params, batch, self.cfg)

    def prefill(self, params, tokens, max_len: int | None = None, counts: bool = False):
        """(last logits, cache), and with ``counts`` the held-expert
        counters (``transformer.prefill``)."""
        max_len = max_len or tokens.shape[1]
        return transformer.prefill(params, tokens, self.cfg, max_len, counts)

    def decode_step(self, params, cache, tokens, counts: bool = False):
        """(logits, cache), and with ``counts`` the step's counters."""
        return transformer.decode_step(params, cache, tokens, self.cfg, counts)

    def init_cache(self, batch: int, max_len: int, start_pos: int = 0):
        return kvcache.init_cache(self.cfg, batch, max_len, start_pos)

    def abstract_cache(self, batch: int, max_len: int):
        return kvcache.abstract_cache(self.cfg, batch, max_len)

    # ----------------------------------------------------------- sampling

    def generate(self, params, prompt, steps: int, temperature: float = 0.0, seed: int = 0):
        """Greedy/temperature sampling for examples & tests (prefill + scan decode)."""
        b, s = prompt.shape
        logits, cache = self.prefill(params, prompt, max_len=s + steps)
        key = jax.random.PRNGKey(seed)

        def pick(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)

        tok = pick(logits, key)
        out = [tok]
        for i in range(steps - 1):
            key, sub = jax.random.split(key)
            logits, cache = self.decode_step(params, cache, tok[:, None])
            tok = pick(logits, sub)
            out.append(tok)
        return jnp.stack(out, axis=1)  # (B, steps)
