"""Batched serving driver: slot-based continuous batching over the decode
step (prefill on arrival, per-slot positions, greedy sampling).

CPU example:
PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
    --slots 4 --requests 8 --prompt-len 12 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class SlotServer:
    """Minimal continuous-batching server over Model.decode_step.

    Fixed `slots` concurrent sequences; free slots accept queued requests;
    each decode step advances every active slot by one token. Per-slot
    positions make the shared KV cache ring-buffer correct.
    """

    def __init__(self, model, *, slots: int, max_seq: int, eos: int | None,
                 max_gen: int):
        self.model = model
        self.slots = slots
        self.max_seq = max_seq
        self.eos = eos
        self.max_gen = max_gen
        self.params = model.init(jax.random.PRNGKey(0))
        self.cache = model.init_cache(slots, max_seq)
        self.pos = np.zeros((slots,), np.int32)
        self.gen_count = np.zeros((slots,), np.int32)
        self.active: list[Request | None] = [None] * slots
        self.cur_tok = np.zeros((slots,), np.int32)
        self._step = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos))

    def _feed_prompt(self, slot: int, req: Request) -> None:
        """Whole-prompt prefill, batched onto the device in one transfer.

        Builds the (S, slots) token/position matrices the token-by-token
        loop would have fed step by step — other slots repeat their current
        token at their current position, an idempotent cache write — ships
        them to the device once, and enqueues S async dispatches of the
        SAME jitted decode step the generation loop runs, syncing the host
        only for the final argmax. Reusing that one compiled executable
        (rather than a separately-jitted scan over the prompt) is what
        makes greedy decode bit-identical to token-by-token stepping: XLA
        gives no cross-program determinism guarantee, and ulp-level logit
        differences between two compilations can flip a near-tie argmax.
        """
        S = len(req.prompt)
        if S == 0:
            raise ValueError(f"request {req.rid} has an empty prompt")
        toks = np.broadcast_to(self.cur_tok, (S, self.slots)).copy()
        toks[:, slot] = np.asarray(req.prompt, np.int32)
        poss = np.broadcast_to(self.pos, (S, self.slots)).copy()
        poss[:, slot] = self.pos[slot] + np.arange(S, dtype=np.int32)
        toks_d, poss_d = jnp.asarray(toks), jnp.asarray(poss)
        logits = None
        for i in range(S):
            logits, self.cache = self._step(
                self.params, self.cache, toks_d[i], poss_d[i])
        self.pos[slot] += S
        self.cur_tok[slot] = int(jnp.argmax(logits[slot]))

    def submit(self, req: Request) -> bool:
        for s in range(self.slots):
            if self.active[s] is None:
                self.active[s] = req
                self.pos[s] = 0
                self.gen_count[s] = 0
                self._feed_prompt(s, req)
                return True
        return False

    def step(self) -> None:
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(self.cur_tok),
            jnp.asarray(self.pos))
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        for s, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[s] += 1
            self.gen_count[s] += 1
            tok = int(nxt[s])
            req.generated.append(tok)
            if ((self.eos is not None and tok == self.eos)
                    or self.gen_count[s] >= self.max_gen
                    or self.pos[s] >= self.max_seq - 1):
                req.done = True
                self.active[s] = None
            else:
                self.cur_tok[s] = tok

    def run(self, queue: list[Request]) -> list[Request]:
        done: list[Request] = []
        pending = list(queue)
        while pending or any(r is not None for r in self.active):
            while pending and self.submit(pending[0]):
                pending.pop(0)
            if any(r is not None for r in self.active):
                self.step()
            for r in queue:
                if r.done and r not in done:
                    done.append(r)
        return done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.family == "encdec":
        raise SystemExit("serve CLI targets decoder families; whisper decode "
                         "is exercised in tests/test_models_decode.py")
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(1, cfg.vocab, size=(args.prompt_len,)))
            for i in range(args.requests)]
    srv = SlotServer(model, slots=args.slots, max_seq=args.max_seq,
                     eos=None, max_gen=args.gen)
    done = srv.run(reqs)
    for r in done:
        print(f"req {r.rid}: prompt[:4]={r.prompt[:4].tolist()} "
              f"-> {len(r.generated)} tokens: {r.generated[:8]}...")
    print(f"[serve] completed {len(done)}/{args.requests} requests")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
