"""Faults planted underneath a cell's timed path, one for each way its
comparison with the plain reference has to catch it:

* `unchanged`: a round that hands its input state back;
* `half_trials`: only the first half of a sweep's trials computed, the rest
  copying them;
* `half_clients`: the mean over the clients taken over the first half of them
  (a cell of one trial leaves half of its batch out so);
* `altered`: the last trial's new iterate nudged by 1% where the round
  produces it.

The tests plant them at a tiny size (`tests/bench/test_bench_drivers.py`);
`bench/control.py --fault` reads them on the chip at the cell's own size.
Plant before anything is compiled.
"""
import jax
import jax.numpy as jnp

FAULTS = ("unchanged", "half_trials", "half_clients", "altered")


class _Patch:
    """What `plant` needs of pytest's `monkeypatch`, without undo."""

    setattr = staticmethod(setattr)

    @staticmethod
    def setitem(d, key, value):
        d[key] = value


def _unchanged(rdef):
    def round_(ops, s, key):
        _, out = rdef.round(ops, s, key)
        return s, (ops.dist_sq(s[0]), out[1])
    return rdef._replace(round=round_)


def _half_trials(rdef):
    def round_(ops, s, key):
        s2, (d2, comm) = rdef.round(ops, s, key)
        if not ops.batched:
            return s2, (d2, comm)
        h = ops.B // 2
        dup = lambda a: jnp.concatenate([a[:h], a[:ops.B - h]]) if a.ndim and a.shape[0] == ops.B else a
        return jax.tree.map(dup, s2), (dup(d2), dup(comm))
    return rdef._replace(round=round_)


def _altered(rdef):
    def round_(ops, s, key):
        s2, (_, comm) = rdef.round(ops, s, key)
        x = s2[0]
        x = x.at[..., -1, :].multiply(1.01) if ops.batched else x * 1.01
        return (x,) + tuple(s2[1:]), (ops.dist_sq(x), comm)
    return rdef._replace(round=round_)


def _half_mean(self, y):
    return jnp.mean(y[..., : y.shape[-2] // 2, :], axis=-2)


def plant(fault: str, algo: str, patch=_Patch) -> None:
    """Plant `fault` in the program's round `algo` (or in its client mean),
    through `patch`'s `setattr` / `setitem`."""
    from repro.core import rounds

    if fault == "half_clients":
        patch.setattr(rounds.RoundOps, "client_mean", _half_mean)
        return
    build = {"unchanged": _unchanged, "half_trials": _half_trials, "altered": _altered}[fault]
    patch.setitem(rounds.ROUND_DEFS, algo, build(rounds.ROUND_DEFS[algo]))
