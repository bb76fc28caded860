"""The paged decode step compiled for a described TPU v5e, at GPT-2 XL's
widths (ISSUE 27). What no CPU test can see: the chip stores the block
pool with the BLOCK axis in the lanes (a head of 64 is half a lane tile),
and a compiler left to itself re-lays the whole pool out to suit an
update and back again, four passes over it a tick. Nothing runs here and
no time is measured: the compiled text is searched for the copies.

One file, and the topology is described inside a fixture, so that only
the worker that is handed this file loads the TPU's library.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.serving import ContinuousGPTEngine

LAYERS, SLOTS, MAX_LEN = 2, 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except NotImplementedError as e:
        # this jax has no TPU topology to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    except RuntimeError as e:
        # the TPU's library is not installed; anything else it says (the
        # library would not load, the name is refused) is a failure
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def _minor_to_major(shape_text):
    return tuple(int(d) for d in
                 re.search(r"\{([\d,]+)[:}]", shape_text).group(1).split(","))


def _device_layout(chip, a):
    """How the described chip lays an array of this shape out, read from
    the parameter of a program that only hands it back."""
    from jax.experimental.layout import Layout

    text = jax.jit(lambda x: x).lower(_on(chip, a)).compile().as_text()
    param = re.search(r"= (\S+) parameter\(0\)", text).group(1)
    return Layout(major_to_minor=_minor_to_major(param)[::-1])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """_paged_step and _paged_verify of an 8 x 1024 engine over two
    GPT-2 XL layers, compiled for the chip with the pool's layouts as the
    chip would store them."""
    cfg = dataclasses.replace(
        GPTConfig.tiny(), vocab_size=512, hidden_size=1600, num_layers=LAYERS,
        num_heads=25, intermediate_size=6400, max_seq_len=MAX_LEN,
        positions="learned", dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: GPTLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=SLOTS, max_len=MAX_LEN,
                              spec_k=4, auto_start=False)
    try:
        pool = eng._pool_kv
        # the engine read the layouts of a pool on THIS host's device;
        # the programs are traced below, for the chip's
        eng._kv_stored = {name: _device_layout(one_chip, a)
                          for name, a in pool.items()}
        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        out = {"pool": pool["k"]}
        for name, fn, toks, k in (
                ("step", eng._paged_step_fn, ints(SLOTS), 1),
                ("verify", eng._paged_verify_fn, ints(SLOTS, 4), 4)):
            out[name] = fn.lower(
                _on(one_chip, variables), _on(one_chip, pool),
                ints(SLOTS, MAX_LEN // 16), ints(SLOTS), toks, k,
                MAX_LEN // 16).compile()
        return out
    finally:
        eng.close()


def _made(text, dims):
    """(opcode, minor-to-major) of every instruction of the compiled text
    that makes an array of these dimensions."""
    want = ",".join(str(d) for d in dims)
    return [(op, _minor_to_major(shape)) for shape, op in re.findall(
        r"= (\w+\[%s\]\{[^ ]*\}) ([\w\-]+)\(" % want, text)]


@pytest.mark.parametrize("which", ["step", "verify"])
def test_the_pool_is_updated_where_it_lies(compiled, which):
    pool = compiled["pool"]
    text = compiled[which].as_text()
    made = _made(text, pool.shape)
    ops = {op for op, _ in made}
    assert "dynamic-update-slice" in ops
    # no copy of the pool, no scatter over it, no change of its axes
    assert not ops & {"copy", "copy-start", "copy-done", "scatter",
                      "transpose"}, sorted(ops)
    # ...and in one layout from the argument to the result: the chip's
    assert len({order for _, order in made}) == 1
    assert made[0][1][0] == 1, "the block axis is no longer the minor one"
    aliases = text.split("input_output_alias={", 1)[1].split(
        "entry_computation_layout", 1)[0]
    assert aliases.count("-alias") == 2


@pytest.mark.parametrize("which", ["step", "verify"])
def test_no_view_over_all_layers_on_the_chip(compiled, which):
    pool = compiled["pool"]
    layers, _, bs, nh, hd = pool.shape
    text = compiled[which].as_text()
    assert _made(text, (layers, SLOTS, MAX_LEN, nh, hd)) == []
    # a layer's gathered rows are there, in some spelling of their shape
    assert (_made(text, (SLOTS, MAX_LEN, nh, hd))
            or _made(text, (SLOTS * MAX_LEN // bs, bs, nh, hd)))
    stats = compiled[which].memory_analysis()
    assert stats.alias_size_in_bytes == 2 * pool.nbytes


# -- the afmoe family at its published widths (ISSUE 29) ----------------------------

@pytest.fixture(scope="module")
def compiled_afmoe(one_chip, monkeypatch_module):
    """The decode step, a ONE-chunk prefill (it gathers a cached prefix
    out of the pool and installs into it) and a FINAL chunk of a 4 x 8192
    engine over
    two layers (one dense sliding, one full expert layer) at Trinity-Mini's
    widths, compiled for the chip: 4 KV heads of 128 are a whole lane tile,
    so the chip keeps the pool ROW-major (a block's bytes together), unlike
    GPT-2 XL's 25 heads of 64 above."""
    from sparkdl_tpu.models.afmoe import (
        FULL,
        SLIDING,
        AfmoeConfig,
        AfmoeLMHeadModel,
    )
    from sparkdl_tpu.parallel import moe_dropless

    # the grouped product the CHIP runs (this process's backend is the CPU)
    monkeypatch_module.setattr(moe_dropless, "auto_interpret", lambda: False)
    cfg = AfmoeConfig(vocab_size=512, num_dense_layers=1,
                      layer_types=(SLIDING, FULL), dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: AfmoeLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    slots, max_len = 4, 8192
    # a pool of 16,384 blocks, as the benchmark's: a small one the compiler
    # would move to faster memory and back
    eng = ContinuousGPTEngine(cfg, variables, n_slots=slots, max_len=max_len,
                              kv_blocks=16384, auto_start=False)
    try:
        pool = eng._pool_kv
        eng._kv_stored = {name: _device_layout(one_chip, a)
                          for name, a in pool.items()}

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        mb = max_len // 16
        private = jax.ShapeDtypeStruct(
            (2, 1, eng._wp, 4, 128), jnp.bfloat16, sharding=one_chip)
        return {
            "pool": pool["k"], "stored": eng._kv_stored["k"],
            "step": eng._paged_step_fn.lower(
                _on(one_chip, variables), _on(one_chip, pool),
                ints(slots, mb), ints(slots), ints(slots), 1, mb).compile(),
            "one": eng._chunk_one_fn.lower(
                _on(one_chip, variables), _on(one_chip, pool), ints(mb),
                ints(), ints(1, 128), ints(mb), 128).compile(),
            "final": eng._chunk_final_fn.lower(
                _on(one_chip, variables), _on(one_chip, pool), private,
                private, ints(), ints(1, 256), ints(mb), max_len).compile(),
        }
    finally:
        eng.close()


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as mp:
        yield mp


@pytest.mark.parametrize("which", ["step", "one", "final"])
def test_a_pool_of_whole_lane_tiles_is_row_major_and_written_in_place(
        compiled_afmoe, which):
    pool = compiled_afmoe["pool"]
    assert compiled_afmoe["stored"].major_to_minor == (0, 1, 2, 3, 4)
    text = compiled_afmoe[which].as_text()
    made = _made(text, pool.shape)
    ops = {op for op, _ in made}
    assert "dynamic-update-slice" in ops
    # the new columns (step) and a prompt's blocks (one, final) go in where
    # the pool lies, and a cached prefix is gathered from where it lies: no
    # copy of the pool, no scatter over it, one layout throughout
    assert not ops & {"copy", "copy-start", "copy-done", "scatter",
                      "transpose"}, sorted(ops)
    assert {order for _, order in made} == {(4, 3, 2, 1, 0)}
    # no layer's slab is sliced out of the pool before its gather
    assert _made(text, (1,) + pool.shape[1:]) == []
    assert compiled_afmoe[which].memory_analysis().alias_size_in_bytes == (
        2 * pool.nbytes)


def test_the_expert_products_are_the_grouped_matmul_kernel(compiled_afmoe):
    text = compiled_afmoe["step"].as_text()
    # three projections of the one expert layer, 4 slots x 8 experts a token
    # padded to a row tile of 128
    calls = re.findall(r"%gmm[.\d]* = bf16\[128,(\d+)\]", text)
    assert sorted(calls) == ["1024", "1024", "2048"]
    # a sliding layer gathers the table entries its window covers, 129 of
    # the 512 a full layer gathers
    assert _made(text, (4, 129, 16, 4, 128)) and _made(text,
                                                       (4, 512, 16, 4, 128))
