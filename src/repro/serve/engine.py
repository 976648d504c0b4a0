"""Ordered serving engine: continuous batching + ordered egress.

This is the paper's workload embodied (DESIGN.md §2): requests arrive with
serial numbers; decode completes out of order (variable generation lengths);
egress must preserve arrival order. The engine is a two-operator pipeline

    prefill (partitioned stateful, keyed by slot)  ->  decode (partitioned)
            -> ordered egress via NonBlockingReorderBuffer

with a CT-style dynamic choice between running a prefill or a decode step
each iteration — the paper's "pipelined flow beats single-operator
saturation" finding shows up as interleave > drain-all-prefills-first.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.reorder import NonBlockingReorderBuffer, ParkingReorderBuffer
from repro.core.serial import SerialAssigner
from repro.models import transformer
from repro.models.common import ModelConfig


@functools.lru_cache(maxsize=None)
def _compiled_fns(cfg: ModelConfig, max_len: int):
    """Shared jitted (prefill, decode) pair, keyed by the only inputs the
    traces close over.  Engines are cheap to construct (tests build one per
    case); without this cache every instance re-traces and re-compiles both
    functions, which dominates wall time and trips suite watchdogs on
    loaded hosts."""
    prefill1 = jax.jit(
        lambda p, t: transformer.prefill(cfg, p, t, max_len=max_len)
    )

    def _decode_fn(p, tok, cache, pos):
        logits, cache = transformer.decode_step(cfg, p, tok, cache, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return prefill1, jax.jit(_decode_fn)


@dataclass
class Request:
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    serial: int = 0
    submitted_at: float = 0.0


@dataclass
class Completion:
    serial: int
    tokens: np.ndarray
    latency_s: float = 0.0


class OrderedServingEngine:
    """Continuous-batching jax model server with ordered completions.

    Requests share ``max_slots`` decode slots (admitted in serial order);
    completions egress through a serial-number reorder ring, so callers see
    results in submission order regardless of per-request decode length —
    the model-serving embodiment of the paper's ordered-egress problem."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_slots: int = 4,
        max_len: int = 96,
        schedule: str = "interleave",  # or "prefill_first" (micro-batch style)
        eos_token: int = -1,
        reorder_size: int = 256,
    ):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.schedule = schedule
        self.eos = eos_token

        self._serials = SerialAssigner()
        self.pending: list[Request] = []
        self.completions: list[Completion] = []
        # Parking wrapper: a slow head-of-line request can hold ``next`` back
        # while more than reorder_size later requests complete. The engine is
        # single threaded, so spinning in send_blocking would livelock —
        # out-of-window completions park host-side and drain as the ring
        # window advances.
        self._reorder = ParkingReorderBuffer(
            NonBlockingReorderBuffer(self._emit, size=reorder_size)
        )

        # slot state (host-side bookkeeping; device-side cache batch = slots)
        self.slot_serial = [-1] * max_slots
        self.slot_generated: list[list[int]] = [[] for _ in range(max_slots)]
        self.slot_budget = [0] * max_slots
        self.slot_t0 = [0.0] * max_slots
        self.position = np.zeros((max_slots,), np.int32)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            transformer.abstract_cache(cfg, max_slots, max_len),
        )
        self.tokens = jnp.zeros((max_slots,), jnp.int32)
        self.active = np.zeros((max_slots,), bool)

        self._prefill1, self._decode = _compiled_fns(cfg, max_len)
        self.stats = {"prefills": 0, "decode_steps": 0, "emitted": 0}

    # ------------------------------------------------------------------ api
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        """Enqueue a prompt; returns its serial (completion order)."""
        serial = self._serials.next()
        self.pending.append(
            Request(np.asarray(prompt, np.int32), max_new_tokens, serial, time.perf_counter())
        )
        return serial

    def prefill_logits(self, prompt: np.ndarray) -> jax.Array:
        """Last-position logits of this engine's prefill step for ``prompt``
        (a request's first token is their argmax); no slot state changes."""
        logits, _ = self._prefill1(
            self.params, np.asarray(prompt, np.int32)[None, :]
        )
        return logits[0]

    def _emit(self, completion: Completion) -> None:
        self.completions.append(completion)
        self.stats["emitted"] += 1

    # ------------------------------------------------------------- internals
    def _free_slot(self) -> Optional[int]:
        for b in range(self.max_slots):
            if not self.active[b]:
                return b
        return None

    def _do_prefill(self) -> None:
        req = self.pending.pop(0)
        b = self._free_slot()
        assert b is not None
        logits, cache1 = self._prefill1(self.params, req.prompt[None, :])
        first = int(jnp.argmax(logits[0]))
        # install the request's KV into slot b (prefill->decode hand-off)
        self.cache = jax.tree.map(
            lambda c, c1: c.at[:, b].set(c1[:, 0]), self.cache, cache1
        )
        self.tokens = self.tokens.at[b].set(first)
        self.position[b] = len(req.prompt)
        self.slot_serial[b] = req.serial
        self.slot_generated[b] = [first]
        self.slot_budget[b] = req.max_new_tokens - 1
        self.slot_t0[b] = req.submitted_at
        self.active[b] = True
        self.stats["prefills"] += 1

    def _do_decode(self) -> None:
        # ``self.position`` is a host buffer mutated in place below (and by
        # ``_do_prefill``).  ``jnp.asarray`` zero-copies 64-byte-aligned numpy
        # arrays on CPU, so handing it over directly lets the in-place update
        # race the asynchronously dispatched decode — the kernel can read a
        # *later* position, silently corrupting the attention mask.  A fresh
        # copy per call is never mutated and stays alive via the jax array.
        next_tok, self.cache = self._decode(
            self.params, self.tokens, self.cache, jnp.asarray(self.position.copy())
        )
        self.tokens = next_tok
        self.position += self.active.astype(np.int32)
        self.stats["decode_steps"] += 1
        toks = np.asarray(next_tok).reshape(-1)
        for b in range(self.max_slots):
            if not self.active[b]:
                continue
            self.slot_generated[b].append(int(toks[b]))
            self.slot_budget[b] -= 1
            done = (
                self.slot_budget[b] <= 0
                or int(toks[b]) == self.eos
                or self.position[b] >= self.max_len - 1
            )
            if done:
                comp = Completion(
                    self.slot_serial[b],
                    np.asarray(self.slot_generated[b], np.int32),
                    time.perf_counter() - self.slot_t0[b],
                )
                # ordered egress: the reorder buffer holds it until all
                # earlier-arrived requests have been emitted; out-of-window
                # completions park (never spin) and drain on later sends
                self._reorder.send(comp.serial, comp)
                self.active[b] = False
                self.slot_serial[b] = -1

    # ------------------------------------------------------------------ run
    def step(self) -> bool:
        """One scheduler decision. Returns False when fully idle."""
        can_prefill = self.pending and self._free_slot() is not None
        can_decode = self.active.any()
        if not can_prefill and not can_decode:
            return False
        if self.schedule == "prefill_first":
            if can_prefill:
                self._do_prefill()
            else:
                self._do_decode()
        else:  # interleave: keep the decode pipeline flowing (CT-style)
            if can_decode and (self.stats["decode_steps"] == 0 or not can_prefill):
                self._do_decode()
            elif can_prefill and self.active.sum() < self.max_slots:
                self._do_prefill()
            else:
                self._do_decode()
        return True

    def run_to_completion(self, max_steps: int = 100_000) -> list[Completion]:
        """Step until every submitted request completed; returns the
        completions drained so far, in serial order."""
        steps = 0
        while self.step():
            steps += 1
            if steps > max_steps:
                raise RuntimeError("engine did not converge")
        return self.completions
