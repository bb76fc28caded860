"""Fixture: donation-safety over the engine's binding form — a module
function with its leading arguments bound, jitted in ``__init__`` under
a ``self._x_fn`` handle (serving/continuous.py since ISSUE 31)."""

import jax

from sparkdl_tpu.serving import paged_programs as programs
from sparkdl_tpu.serving.paged_programs import bound


class Engine:
    def __init__(self, sizes, model):
        self._step_fn = jax.jit(
            bound(programs._paged_step, sizes, model),
            donate_argnums=(1,), static_argnums=(5, 6))
        self._fetch_fn = jax.jit(programs._park_fetch)

    def decode(self, variables, table, idx, tok):
        toks, self._pool_kv = self._step_fn(
            variables, self._pool_kv, table, idx, tok, 1, 4)
        return toks, self._pool_kv  # rebound by the call statement: safe

    def leak(self, variables, table, idx, tok):
        toks, pool = self._step_fn(
            variables, self._pool_kv, table, idx, tok, 1, 4)
        return toks, self._pool_kv  # VIOLATION: self._pool_kv is dead

    def fetch(self, ids):
        out = self._fetch_fn(self._pool_kv, ids)
        return out, self._pool_kv  # _fetch_fn donates nothing: clean
