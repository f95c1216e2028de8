"""Batched serving: prefill + greedy decode with a sharded KV cache.

``make_serve_step`` builds the single-token decode program the dry-run
lowers for the ``decode_*`` / ``long_*`` shapes: one new token against a
``seq_len`` KV cache.  The cache's sequence dim is sharded over ``model``
(flash-decoding; the paper's *chaining* across chips), batch over the data
axes; for batch-1 long-context decode the sequence shards over both axes.

``ServeEngine`` is the small driver used by examples/serve_demo.py: fixed
batch slots, greedy sampling, per-slot stop handling (continuous-batching
lite).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend.serve_bridge import pad_to_slots
from repro.models import decode_step, init_kv_cache
from repro.models.config import ModelConfig


def kv_cache_specs(plan, cache_shapes: Dict) -> Dict:
    """PartitionSpecs for every cache entry, by shape."""
    from jax.sharding import PartitionSpec as P

    mesh = plan.mesh
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    msize = mesh.shape["model"]

    ATTN = ("k", "v", "shared_k", "shared_v")

    def spec_for(name: str, shape) -> P:
        # attention caches are (L|napp, B, H, S, D): seq is dim 3
        batch = shape[1]
        entries = [None] * len(shape)
        if batch % dpn == 0 and batch >= dpn:
            entries[1] = dp
            if name in ATTN and shape[3] % msize == 0:
                entries[3] = "model"        # seq over model (flash-decoding)
        elif name in ATTN:
            total = dpn * msize
            if shape[3] % total == 0:
                entries[3] = dp + ("model",)  # batch-1: seq over everything
            elif shape[3] % msize == 0:
                entries[3] = "model"
        else:
            # ssm states with undivisible batch: shard heads over model
            if len(shape) >= 3 and shape[2] % msize == 0:
                entries[2] = "model"
        return P(*entries)

    return {k: spec_for(k, v.shape) for k, v in cache_shapes.items()}


def make_serve_step(cfg: ModelConfig, greedy: bool = True) -> Callable:
    """(params, cache, tokens (B,), pos scalar) -> (next_tokens, cache)."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = decode_step(cfg, params, cache, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    return serve_step


@dataclass
class Request:
    prompt: List[int]
    max_new: int = 16
    generated: List[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Fixed-slot batched greedy decoding (continuous-batching lite)."""

    def __init__(self, cfg: ModelConfig, params, batch_slots: int, max_seq: int):
        self.cfg = cfg
        self.params = params
        self.batch = batch_slots
        self.max_seq = max_seq
        self.cache = init_kv_cache(cfg, batch_slots, max_seq, dtype=jnp.float32)
        self.step_fn = jax.jit(make_serve_step(cfg))
        self.pos = 0

    def run(self, requests: List[Request]) -> List[Request]:
        reqs = pad_to_slots(
            requests, self.batch, lambda: Request(prompt=[0], max_new=0)
        )
        max_prompt = max(len(r.prompt) for r in reqs)
        total = max_prompt + max(r.max_new for r in reqs)
        assert total <= self.max_seq
        tok = np.zeros((self.batch,), np.int32)
        for t in range(total - 1):
            for i, r in enumerate(reqs):
                if t < len(r.prompt):
                    tok[i] = r.prompt[t]
            nxt, self.cache = self.step_fn(
                self.params, self.cache, jnp.asarray(tok), t
            )
            nxt = np.asarray(nxt)
            for i, r in enumerate(reqs):
                # the model's prediction becomes input once the prompt is done
                if t + 1 >= len(r.prompt) and not r.done:
                    if len(r.generated) < r.max_new:
                        r.generated.append(int(nxt[i]))
                        tok[i] = int(nxt[i])
                    else:
                        r.done = True
        return reqs


__all__ = [
    "ServeEngine",
    "Request",
    "make_serve_step",
    "kv_cache_specs",
    "pad_to_slots",
]
