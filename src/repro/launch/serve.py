"""Batched serving driver: continuous-batching loop over prefill + decode.

Requests arrive with different prompt lengths; the scheduler packs them into
a fixed-size decode batch (padding slots), prefills new requests into free
slots, and steps the whole batch one token at a time — the standard
batched-serving shape (decode_32k cell = one such step at scale).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.data.pipeline import batch_kwargs_for
from repro.launch.cache import setup_compile_cache
from repro.models.model import build_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class BatchServer:
    """Static-batch server (slots = batch size); greedy sampling."""

    def __init__(self, arch: str, *, reduced: bool = True, slots: int = 4,
                 s_max: int = 128, seed: int = 0):
        self.cfg = get_reduced(arch) if reduced else get_config(arch)
        self.model = build_model(self.cfg, attn_impl="ref",
                                 remat_policy="none", loss_chunk=1024)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.slots = slots
        self.s_max = s_max
        self.active: List[Optional[Request]] = [None] * slots
        self.caches: List[Any] = [None] * slots
        self._decode = jax.jit(self.model.decode_step)

    # one slot per request keeps per-request cache lengths exact; a
    # production deployment fuses slots into one batched cache (the
    # decode_32k dry-run cell models that shape)
    def submit(self, req: Request) -> bool:
        for i in range(self.slots):
            if self.active[i] is None:
                prompt = jnp.asarray([req.prompt], jnp.int32)
                cache, logits = self.model.prefill(
                    self.params, {"tokens": prompt}, self.s_max)
                tok = int(jnp.argmax(logits, -1)[0])
                req.out.append(tok)
                self.active[i] = req
                self.caches[i] = cache
                return True
        return False

    def step(self) -> int:
        """Advance every active request one token; returns #active."""
        n = 0
        for i, req in enumerate(self.active):
            if req is None:
                continue
            n += 1
            tok = jnp.asarray([[req.out[-1]]], jnp.int32)
            self.caches[i], logits = self._decode(self.params,
                                                  self.caches[i],
                                                  {"tokens": tok})
            nxt = int(jnp.argmax(logits, -1)[0])
            req.out.append(nxt)
            if len(req.out) >= req.max_new:
                req.done = True
                self.active[i] = None
                self.caches[i] = None
        return n

    def run(self, requests: List[Request]) -> Dict[str, Any]:
        t0 = time.time()
        pending = list(requests)
        done: List[Request] = []
        tokens = 0
        while pending or any(r is not None for r in self.active):
            while pending and self.submit(pending[0]):
                pending.pop(0)
            tokens += self.step()
            done = [r for r in requests if r.done]
        dt = time.time() - t0
        return {"requests": len(requests), "tokens": tokens,
                "wall_s": round(dt, 3),
                "tok_per_s": round(tokens / max(dt, 1e-9), 1),
                "completed": len(done)}


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma_2b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()
    server = BatchServer(args.arch)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, server.cfg.vocab_size,
                                        rng.integers(4, 16)).tolist(),
                    max_new=args.max_new)
            for i in range(args.requests)]
    print(json.dumps(server.run(reqs)))


if __name__ == "__main__":
    main()
