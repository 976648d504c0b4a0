"""Faults planted under the timed path, and the control, for the tests
that show the comparison catches them.  The benchmark's own runs plant
nothing: ``kernel_for(None)`` is the program's kernel and
``wrap_ops(ops, None)`` returns the operators as built.

Faults, each where the timed path produces it:

- ``device_identity``: the device stage returns its input unchanged;
- ``half_batch``: the device stage converts the first half of each
  dispatch and leaves the rest out;
- ``device_float32``: the device stage computes its affine map in
  float32, below the int32 its schema states;
- ``low_digit``: the device stage adds one mill to the last column of
  the first row of each dispatch, an error that a floor division back to
  cents would hide;
- ``state_unchanged``: the keyed operator returns the state it was given;
- ``answer_altered``: the last operator alters one answer in 4,096.

Controls, each breaking a guarantee the configurations state
(exactly-once delivery in serial order) in what the harness read:

- ``duplicate_delivery``: one answer delivered twice;
- ``reorder_pair``: two neighbouring answers swapped.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

KERNEL = "affine_pallas"
FAULTS = ("device_identity", "half_batch", "device_float32", "low_digit",
          "state_unchanged", "answer_altered")
CONTROLS = ("duplicate_delivery", "reorder_pair")


def _np_identity(params):
    return lambda *cols: tuple(cols)


def _np_half(params):
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        out = []
        for c in cols:
            c = c.copy()
            h = len(c) // 2
            c[:h] = c[:h] * a + b
            out.append(c)
        return tuple(out)

    return fn


def _jax_half(params):
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        import jax.numpy as jnp
        from repro.columnar.device import affine_pallas

        h = cols[0].shape[0] // 2
        return tuple(jnp.concatenate([affine_pallas(c[:h], a, b), c[h:]])
                     for c in cols)

    return fn


def _np_float32(params):
    kw = dict(params)
    a, b = np.float32(kw.get("a", 1)), np.float32(kw.get("b", 0))
    return lambda *cols: tuple(
        (c.astype(np.float32) * a + b).astype(c.dtype) for c in cols)


def _jax_float32(params):
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        import jax.numpy as jnp

        return tuple((c.astype(jnp.float32) * jnp.float32(a)
                      + jnp.float32(b)).astype(c.dtype) for c in cols)

    return fn


def _np_low_digit(params):
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        out = [c * a + b for c in cols]
        out[-1][0] += 1
        return tuple(out)

    return fn


def _jax_low_digit(params):
    kw = dict(params)
    a, b = kw.get("a", 1), kw.get("b", 0)

    def fn(*cols):
        from repro.columnar.device import affine_pallas

        out = [affine_pallas(c, a, b) for c in cols]
        out[-1] = out[-1].at[0].add(1)
        return tuple(out)

    return fn


DEVICE_FAULTS = {
    "device_identity": (_np_identity, _np_identity),
    "half_batch": (_np_half, _jax_half),
    "device_float32": (_np_float32, _jax_float32),
    "low_digit": (_np_low_digit, _jax_low_digit),
}


def kernel_for(fault: str | None) -> str:
    """The device kernel's registered name for this run."""
    if fault not in DEVICE_FAULTS:
        return KERNEL
    from repro.columnar.device import KERNELS

    name = f"bench_fault_{fault}"
    KERNELS[name] = DEVICE_FAULTS[fault]
    return name


def _keep_state(fn, state, key, t):
    _, outs = fn(state, key, t)
    return state, outs


def _alter(fn, t):
    outs = fn(t)
    return [o[:-1] + (o[-1] + 1,) if o[0] % 4096 == 7 else o for o in outs]


def _alter_keyed(fn, state, key, t):
    state, outs = fn(state, key, t)
    return state, [o[:-1] + (o[-1] + 1,) if o[0] % 4096 == 7 else o
                   for o in outs]


def wrap_ops(ops: list, fault: str | None) -> list:
    """The operators with ``fault`` planted in them."""
    if fault is None or fault in DEVICE_FAULTS:
        return ops
    last = ops[-1]
    if fault == "state_unchanged":
        keyed = [i for i, op in enumerate(ops) if op.kind == "partitioned"]
        if not keyed:
            raise ValueError("state_unchanged needs a keyed operator")
        i = keyed[0]
        ops = list(ops)
        ops[i] = dataclasses.replace(
            ops[i], fn=functools.partial(_keep_state, ops[i].fn))
        return ops
    if fault == "answer_altered":
        wrap = _alter_keyed if last.kind == "partitioned" else _alter
        return ops[:-1] + [dataclasses.replace(
            last, fn=functools.partial(wrap, last.fn))]
    raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")


def apply_control(got: np.ndarray, control: str | None,
                  seed: int) -> np.ndarray:
    """What the harness read, with ``control``'s guarantee broken at a row
    drawn from the seed."""
    if control is None or len(got) < 2:
        return got
    k = int(np.random.default_rng(seed).integers(0, len(got) - 1))
    if control == "duplicate_delivery":
        return np.insert(got, k + 1, got[k], axis=0)
    if control == "reorder_pair":
        got = got.copy()
        got[[k, k + 1]] = got[[k + 1, k]]
        return got
    raise ValueError(f"unknown control {control!r} (have {CONTROLS})")
