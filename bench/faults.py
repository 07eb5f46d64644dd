"""Named controls and faults planted in a cell's timed path.

The correctness check must separate a sound run from these.  A *control*
breaks one guarantee the configuration states, in the way a later change
might be tempted to (``prefix32``: answer a seed by its first 32 symbols
only; ``early_stop``: sort the suffixes by their first 32 symbols only).
A *fault* breaks the path outright.  ``run.py --fault NAME`` selects one;
the benchmark's own runs plant none.  Each is planted through a public
name that the drivers already call through (the server's ``submit`` and
``flush``, ``pipeline.suffix_array_fast``, ``pipeline.bwt_from_sa``),
never through the program's private helpers.
"""

from __future__ import annotations

import contextlib

import numpy as np

SERVING = ("prefix32", "count_plus_one", "shift_position", "drop_half")
BUILD = ("early_stop", "state_unchanged", "bwt_flip")


def apply_serving(name: str | None, timed_server) -> None:
    """Patch the program's ``FMQueryServer`` behind the benchmark's
    ``TimedServer`` in place."""
    if name is None:
        return
    if name not in SERVING:
        raise ValueError(f"unknown serving fault {name!r}; known {SERVING}")
    srv = timed_server._server
    submit, flush = srv.submit, srv.flush

    if name == "prefix32":
        srv.submit = lambda pattern, kind="count", k=None: submit(
            np.asarray(pattern)[:32], kind, k)
        return

    def altered():
        out = flush()
        keys = sorted(out)
        if name == "drop_half":
            for t in keys[::2]:
                del out[t]
            return out
        for t in keys:
            r = out[t]
            if name == "count_plus_one" and r.kind == "count":
                r.count += 1
                break
            if (name == "shift_position" and r.kind == "locate"
                    and r.count > 0):
                r.positions = r.positions + 1
                break
        return out

    srv.flush = altered


PREFIX_SYMBOLS = 32   # the early_stop control sorts suffixes by this many


def prefix_suffix_array(s, sigma: int, **_):
    """Suffixes sorted by their first ``PREFIX_SYMBOLS`` symbols only, ties
    by position: the suffix sort stopped before every suffix is told
    apart (sorted on the host).  Returns ``(sa, None)`` like
    ``suffix_array_fast``."""
    import jax.numpy as jnp

    s = np.asarray(s).astype(np.uint64)
    n = len(s)
    bits = max(1, int(sigma - 1).bit_length())
    per = 64 // bits
    ext = np.concatenate([s, np.zeros(PREFIX_SYMBOLS, np.uint64)])
    keys = []
    for w in range(0, PREFIX_SYMBOLS, per):
        k = np.zeros(n, np.uint64)
        for j in range(w, min(w + per, PREFIX_SYMBOLS)):
            k = (k << np.uint64(bits)) | ext[j : j + n]
        keys.append(k)
    order = np.lexsort((np.arange(n), *reversed(keys)))
    return jnp.asarray(order.astype(np.int32)), None


def unchanged_suffix_array(s, sigma: int, **_):
    """The sort's state returned as it came in: text order."""
    import jax.numpy as jnp

    return jnp.arange(jnp.asarray(s).shape[0], dtype=jnp.int32), None


@contextlib.contextmanager
def build_fault(name: str | None):
    """Swap the suffix sort or the BWT step that ``pipeline`` calls for
    the duration of the block."""
    if name is None:
        yield
        return
    if name not in BUILD:
        raise ValueError(f"unknown build fault {name!r}; known {BUILD}")
    from repro.core import pipeline

    attr = "bwt_from_sa" if name == "bwt_flip" else "suffix_array_fast"
    orig = getattr(pipeline, attr)

    def flipped(s, sa):
        bwt, row = orig(s, sa)
        i = bwt.shape[0] // 2
        return bwt.at[i].set(bwt[i] % 3 + 1), row

    setattr(pipeline, attr, {"bwt_flip": flipped,
                             "early_stop": prefix_suffix_array,
                             "state_unchanged": unchanged_suffix_array}[name])
    try:
        yield
    finally:
        setattr(pipeline, attr, orig)
