"""The engine's device programs as ``serving/paged_programs.py`` names
them (ISSUE 31): each keeps the name the benchmark finds it by in a device
trace, and each can be lowered from ``(sizes, module, arrays)`` with no
engine around it. CPU: text and shapes only, nothing is timed."""

import jax
import jax.numpy as jnp
import pytest

from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving import paged_programs as programs

SLOTS, MAX_LEN = 2, 32


def _abstract(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _ints(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.fixture(scope="module")
def lowered():
    """Every program of a tiny paged engine that the benchmark reads by
    name, lowered from the handle the engine dispatches through."""
    cfg = GPTConfig.tiny()
    variables = jax.eval_shape(
        lambda: GPTLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=SLOTS, max_len=MAX_LEN,
                              auto_start=False)
    try:
        v, pool, mb = _abstract(variables), _abstract(eng._pool_kv), eng._mb
        private = jax.ShapeDtypeStruct(
            (cfg.num_layers, 1, eng._wp) + pool["k"].shape[3:], cfg.dtype)
        return {
            "_paged_step": eng._paged_step_fn.lower(
                v, pool, _ints(SLOTS, mb), _ints(SLOTS), _ints(SLOTS),
                _ints(SLOTS), 1, mb),
            "_chunk_one": eng._chunk_one_fn.lower(
                v, pool, _ints(mb), _ints(), _ints(1, 8), _ints(mb), 8),
            "_chunk_first": eng._chunk_first_fn.lower(
                v, pool, _ints(mb), _ints(), _ints(1, 8), 8),
            "_chunk_mid": eng._chunk_mid_fn.lower(
                v, private, private, _ints(), _ints(1, 8), 16),
            "_chunk_final": eng._chunk_final_fn.lower(
                v, pool, private, private, _ints(), _ints(1, 8), _ints(mb),
                16),
        }
    finally:
        eng.close()


@pytest.mark.parametrize("name", ["_paged_step", "_chunk_one", "_chunk_first",
                                  "_chunk_mid", "_chunk_final"])
def test_the_device_shows_each_program_under_the_name_the_benchmark_reads(
        lowered, name):
    """The benchmark finds programs in a device trace by name:
    ``benchmark/readers.py:47`` sums the executions whose name holds
    ``paged_step`` (``decode_device_ms``, ``decode_roofline_share``),
    ``benchmark/readers_afmoe.py:137`` the same (``expert_device_ms``, both
    ``.mixed`` roofline shares), ``benchmark/engine_readers.py:100`` those
    that hold ``_chunk_`` (``prefill_device_share``). The device names a
    program ``jit_`` + the ``__name__`` of the function ``jax.jit`` was
    given; a bare ``functools.partial`` has none and lowers as
    ``jit__unknown``, which every CPU test survives and every one of those
    metrics reads as null."""
    first_line = lowered[name].as_text().splitlines()[0]
    assert first_line.startswith(f"module @jit_{name} "), first_line


def test_a_program_lowers_from_sizes_module_and_arrays_alone():
    """No engine, queue or thread: the decode step of a 2 x 32 paged
    deployment from what ``paged_programs`` takes, and the pool goes out
    in the shape it came in."""
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))
    bs, mb = 16, MAX_LEN // 16
    sizes = programs.PagedSizes(
        n_slots=SLOTS, block_size=bs, mb=mb, w=mb * bs, wp=mb * bs + 8,
        max_pos=mb * bs + 16, dtype=cfg.dtype)
    pool = jax.eval_shape(lambda: kv_pool.init_block_pool(cfg, 4, bs))
    step = jax.jit(programs.bound(programs._paged_step, sizes, model),
                   donate_argnums=(1,), static_argnums=(6, 7))
    low = step.lower(variables, pool, _ints(SLOTS, mb), _ints(SLOTS),
                     _ints(SLOTS), _ints(SLOTS), 1, mb)
    assert low.as_text().startswith("module @jit__paged_step ")
    # the ids for the host, and every slot's last one again for the step
    # that is launched before the host has read them
    toks, last, out = low.out_info
    assert toks.shape == (1, SLOTS) and last.shape == (SLOTS,)
    assert {n: a.shape for n, a in out.items()} == {
        n: a.shape for n, a in pool.items()}
