"""The paged decode programs compiled for a described TPU v5e, at GPT-2
XL's widths (ISSUE 27, ISSUE 30). What no CPU test can see: how the chip
lays the block pool out. With heads and head size apart, ``[.., 25, 64]``,
it put the BLOCK axis in the lanes (a head of 64 is half a lane tile), and
every gather through the table transposed a layer's slab first. Stored on
one merged axis padded to whole tiles, ``[layers, blocks, 16, 1664]``, the
pool lies with layers and blocks major, and the programs read and write it
where it lies. Nothing runs here and no time is measured: the compiled text
is searched for the layouts and the copies.

One file, and the topology is described inside a fixture, so that only
the worker that is handed this file loads the TPU's library.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.runtime.chip import alike_layers_options
from sparkdl_tpu.serving import ContinuousGPTEngine, continuous

#: a pool of 640 blocks, so that a layer's slab ``[640, 16, ..]`` and the 8
#: x 64 blocks a step gathers are different shapes
LAYERS, SLOTS, MAX_LEN, BLOCKS = 2, 8, 1024, 640


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


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as mp:
        yield mp


@pytest.fixture(scope="module")
def for_the_chip(monkeypatch_module):
    """This process's backend is the CPU, and an engine built here asks
    for no compile option; the programs below are compiled FOR the chip, so
    they take the options the engine gives them there."""
    monkeypatch_module.setattr(
        continuous, "alike_layers_options",
        lambda: alike_layers_options("tpu"))


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
def compiled(one_chip, for_the_chip):
    """The decode step, a chained step of 4, the verify pass of 4, a
    ONE-chunk prefill (it gathers a cached prefix out of the pool and
    installs into it) and a FINAL chunk of an 8 x 1024 engine over two
    GPT-2 XL layers, compiled for the chip."""
    cfg = dataclasses.replace(
        GPTConfig.tiny(), vocab_size=512, hidden_size=1600, num_layers=LAYERS,
        num_heads=25, intermediate_size=6400, max_seq_len=MAX_LEN,
        positions="learned", dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: GPTLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=SLOTS, max_len=MAX_LEN,
                              kv_blocks=BLOCKS, spec_k=4, auto_start=False)
    try:
        pool = eng._pool_kv

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        mb = MAX_LEN // 16
        # a prompt's private prefill cache keeps a token as the pool does
        private = jax.ShapeDtypeStruct(
            (LAYERS, 1, eng._wp) + pool["k"].shape[3:], jnp.bfloat16,
            sharding=one_chip)
        head = (_on(one_chip, variables), _on(one_chip, pool))
        out = {"pool": pool["k"],
               "stored": _device_layout(one_chip, pool["k"])}
        # (a step's tokens: the host's, and the step before's on the device)
        for name, fn, toks, k in (
                ("step", eng._paged_step_fn, (ints(SLOTS), ints(SLOTS)), 1),
                ("chain", eng._paged_step_fn, (ints(SLOTS), ints(SLOTS)), 4),
                ("verify", eng._paged_verify_fn, (ints(SLOTS, 4),), 4)):
            out[name] = fn.lower(
                *head, ints(SLOTS, mb), ints(SLOTS), *toks, k, mb).compile()
        out["one"] = eng._chunk_one_fn.lower(
            *head, ints(mb), ints(), ints(1, 128), ints(mb), 128).compile()
        out["final"] = eng._chunk_final_fn.lower(
            *head, private, private, ints(), ints(1, 256), ints(mb),
            MAX_LEN).compile()
        return out
    finally:
        eng.close()


def _made(text, dims):
    """(opcode, minor-to-major) of every instruction of the compiled text
    that makes an array of these dimensions."""
    want = ",".join(str(d) for d in dims)
    return [(op, _minor_to_major(shape)) for shape, op in re.findall(
        r"= (\w+\[%s\]\{[^ ]*\}) ([\w\-]+)\(" % want, text)]


def _gmm_kernel_blocks(lowered_text):
    """The kernel block ``[tk, tn]`` of each grouped matmul a LOWERED text
    holds (one for each shape of kernels: the calls of one shape share a
    function), as ``"2048x512"``, read off the signature of its decoded
    Mosaic body (megablox names its kernel ``kernel``)."""
    plain, _ = _without_locations(lowered_text)
    return re.findall(
        r"module @kernel [^\n]*\n[^\n]*\n *\^bb0\([^\n]*?"
        r"memref<1x(\d+x\d+)xbf16, #tpu\.memory_space<vmem>>", plain)


def test_the_merged_axis_keeps_layers_and_blocks_major_on_the_chip(
        one_chip, compiled):
    pool = compiled["pool"]
    # 25 heads of 64 on one axis, padded to 13 whole lane tiles
    assert pool.shape == (LAYERS, BLOCKS, 16, 1664)
    assert compiled["stored"].major_to_minor == (0, 1, 2, 3)
    # what the pad is for: 1600 is 12.5 tiles, and the chip then takes the
    # block axis for its lanes, as it did with [.., 25, 64]
    unpadded = jax.ShapeDtypeStruct((48, 512, 16, 1600), jnp.bfloat16)
    assert _device_layout(one_chip, unpadded).major_to_minor[-1] == 1


@pytest.mark.parametrize(
    "which", ["step", "chain", "verify", "one", "final"])
def test_the_pool_is_read_and_written_where_it_lies(compiled, which):
    pool = compiled["pool"]
    text = compiled[which].as_text()
    made = _made(text, pool.shape)
    ops = {op for op, _ in made}
    # the new columns as ONE scatter a pool array (step, chain, verify), a
    # prompt's blocks as a loop of updates (one, final): in place
    assert ops & {"scatter", "dynamic-update-slice"}, sorted(ops)
    # no copy of the pool, no change of its axes
    assert not ops & {"copy", "copy-start", "copy-done", "transpose"}, (
        sorted(ops))
    # ...in one layout from the argument to the result: the stored one
    assert {order for _, order in made} == {(3, 2, 1, 0)}
    # no layer's slab sliced or copied out of the pool before its gather,
    # in either spelling of its shape
    _, blocks, bs, merged = pool.shape
    assert _made(text, (1, blocks, bs, merged)) == []
    assert _made(text, (blocks, bs, merged)) == []
    # every pool array goes out in the buffer it came in
    aliases = text.split("input_output_alias={", 1)[1].split(
        "entry_computation_layout", 1)[0]
    assert aliases.count("-alias") == 2
    stats = compiled[which].memory_analysis()
    assert stats.alias_size_in_bytes == 2 * pool.nbytes
    # ...and nothing of the pool's size is held beside it
    assert stats.temp_size_in_bytes < pool.nbytes / 4


@pytest.mark.parametrize("which", ["step", "chain", "verify"])
def test_attention_takes_the_gathered_rows_as_they_lie(compiled, which):
    pool = compiled["pool"]
    layers, _, bs, merged = pool.shape
    text = compiled[which].as_text()
    # no view over all layers, and no rows with heads and head size apart:
    # [S, W, 25, 64] is the padded copy the parent's attention read
    assert _made(text, (layers, SLOTS, MAX_LEN, merged)) == []
    for rows in ((SLOTS, MAX_LEN), (SLOTS, MAX_LEN // bs, bs),
                 (SLOTS * MAX_LEN // bs, bs)):
        assert _made(text, rows + (25, 64)) == []
    # a layer's gathered rows are there on the merged axis, and nothing
    # copies or transposes them on their way into the products
    gathered = (_made(text, (SLOTS, MAX_LEN, merged))
                + _made(text, (SLOTS * MAX_LEN // bs, bs, merged))
                + _made(text, (SLOTS, MAX_LEN // bs, bs, merged)))
    assert gathered
    assert all(order[0] == len(order) - 1 for _, order in gathered)
    assert not {op for op, _ in gathered} & {"copy", "copy-start"}
    # the scores and the weighted sum are products over the merged axis
    assert re.search(r"f32\[%d,\d+,%d\]\S* convolution\(" % (
        SLOTS, MAX_LEN), text)


def test_the_layers_of_a_chunk_share_their_code(one_chip, for_the_chip):
    """All 48 layers this once: a one-chunk prefill, the program a serving
    engine holds most of, compiled as the engine compiles it on the chip
    (``runtime.chip.alike_layers_options``). Its layers' operations are
    compiled once and called; left to the compiler's own rule this program
    is 116 MB of generated code (3 MB a layer), which every start then
    loads from the cache (PERF.md section 6, PR 30)."""
    cfg = dataclasses.replace(
        GPTConfig.tiny(), vocab_size=512, hidden_size=1600, num_layers=48,
        num_heads=25, intermediate_size=6400, max_seq_len=MAX_LEN,
        positions="learned", dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: GPTLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=SLOTS, max_len=MAX_LEN,
                              auto_start=False)
    try:
        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        mb = MAX_LEN // 16
        one = eng._chunk_one_fn.lower(
            _on(one_chip, variables), _on(one_chip, eng._pool_kv), ints(mb),
            ints(), ints(1, 256), ints(mb), 256).compile()
    finally:
        eng.close()
    stats = one.memory_analysis()
    assert stats.generated_code_size_in_bytes < 24e6
    # still in place: the pool goes out in the buffer it came in
    assert stats.alias_size_in_bytes == 2 * eng._pool_kv["k"].nbytes


# -- the afmoe family at its published widths (ISSUE 29) ----------------------

@pytest.fixture(scope="module")
def compiled_afmoe(one_chip, for_the_chip, monkeypatch_module):
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

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        mb = max_len // 16
        private = jax.ShapeDtypeStruct(
            (2, 1, eng._wp, 4, 128), jnp.bfloat16, sharding=one_chip)
        lowered = eng._paged_step_fn.lower(
            _on(one_chip, variables), _on(one_chip, pool),
            ints(slots, mb), ints(slots), ints(slots), ints(slots), 1, mb)
        return {
            "pool": pool["k"],
            "stored": _device_layout(one_chip, pool["k"]),
            "step": lowered.compile(),
            "step_lowered": lowered.as_text(),
            "one": eng._chunk_one_fn.lower(
                _on(one_chip, variables), _on(one_chip, pool), ints(mb),
                ints(), ints(1, 128), ints(mb), 128).compile(),
            "final": eng._chunk_final_fn.lower(
                _on(one_chip, variables), _on(one_chip, pool), private,
                private, ints(), ints(1, 256), ints(mb), max_len).compile(),
        }
    finally:
        eng.close()


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
    # widths of 2,048 and 1,024 keep the kernel blocks they had (ISSUE 46)
    assert _gmm_kernel_blocks(compiled_afmoe["step_lowered"]) == [
        "1024x1024"] * 2
    # a sliding layer gathers the table entries its window covers, 129 of
    # the 512 a full layer gathers
    assert _made(text, (4, 129, 16, 4, 128)) and _made(text,
                                                       (4, 512, 16, 4, 128))


# -- the olmo_hybrid family at its published widths (ISSUE 34) ------------------

@pytest.fixture(scope="module")
def compiled_hybrid(one_chip, for_the_chip, monkeypatch_module):
    """The decode step, a MID chunk (the running state
    goes in and comes out) and a FINAL chunk (it installs K/V into the
    slot's blocks and the state into the slot's row) of a 16 x 8192 engine
    over one linear-attention and one full-attention layer at
    Olmo-Hybrid-7B's widths, compiled for the chip."""
    from sparkdl_tpu.models.olmo_hybrid import (
        FULL,
        LINEAR,
        OlmoHybridConfig,
        OlmoHybridLMHeadModel,
    )
    from sparkdl_tpu.ops import delta_solve, paged_decode

    # the paged attention and the triangular solve the CHIP runs (this
    # process's backend is the CPU)
    monkeypatch_module.setattr(paged_decode, "auto_interpret", lambda: False)
    monkeypatch_module.setattr(delta_solve, "auto_interpret", lambda: False)
    cfg = OlmoHybridConfig(vocab_size=512, layer_types=(LINEAR, FULL),
                           dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: OlmoHybridLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    slots, max_len = 16, 8192
    eng = ContinuousGPTEngine(cfg, variables, n_slots=slots, max_len=max_len,
                              auto_start=False)
    try:
        pool = eng._pool_kv

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        mb = max_len // 16
        private = jax.ShapeDtypeStruct(
            (1, 1, eng._wp, 3840), jnp.bfloat16, sharding=one_chip)
        rec = {name: jax.ShapeDtypeStruct(
            (1, 1) + a.shape[2:], a.dtype, sharding=one_chip)
            for name, a in pool.items() if name in ("state", "conv")}
        head = (_on(one_chip, variables), _on(one_chip, pool))
        step = (ints(slots, mb), ints(slots), ints(slots), ints(slots))
        lowered = eng._paged_step_fn.lower(*head, *step, 1, mb)
        return {
            "pool": pool,
            "stored": {name: _device_layout(one_chip, a)
                       for name, a in pool.items()},
            "step": lowered.compile(),
            "step_lowered": lowered.as_text(),
            "mid": eng._chunk_mid_fn.lower(
                head[0], private, private, ints(), ints(1, 256), 4096,
                ints(), rec).compile(),
            "final": eng._chunk_final_fn.lower(
                *head, private, private, ints(), ints(1, 256), ints(mb),
                max_len, ints(), rec, ints()).compile(),
        }
    finally:
        eng.close()


def test_thirty_heads_of_128_are_stored_on_one_axis_and_why(one_chip,
                                                            compiled_hybrid):
    """30 heads of 128 kept apart, ``[.., 16, 30, 128]``, the chip stores
    with the block's 16 tokens in the sublanes and the heads outside them
    (30 is no whole count of sublane tiles), and a program that writes a
    column copies the whole pool to the other order and back. Side by side
    on one axis the pool is row-major, as GPT-2 XL's; a count of heads that
    is 2, 4 or a multiple of 8 keeps its own axis row-major (afmoe's 4)."""
    pool = compiled_hybrid["pool"]
    assert pool["k"].shape == (1, 8192, 16, 3840)
    assert compiled_hybrid["stored"]["k"].major_to_minor == (0, 1, 2, 3)
    apart = jax.ShapeDtypeStruct((2, 8192, 16, 30, 128), jnp.bfloat16)
    assert _device_layout(one_chip, apart).major_to_minor == (0, 1, 3, 2, 4)
    for heads in (2, 4, 8, 16, 32):
        kept = jax.ShapeDtypeStruct((2, 1024, 16, heads, 128), jnp.bfloat16)
        assert _device_layout(one_chip, kept).major_to_minor == (
            0, 1, 2, 3, 4), heads
    # the state by slot lies as it is indexed (its 192 values a row are
    # padded to two lane tiles), the tails with the slots in the sublanes
    assert pool["state"].shape == (1, 16, 30, 96, 192)
    assert compiled_hybrid["stored"]["state"].major_to_minor == (
        0, 1, 2, 3, 4)
    assert pool["conv"].shape == (1, 16, 3, 11520)


@pytest.mark.parametrize("which", ["step", "final"])
def test_neither_the_pool_nor_the_state_is_copied(compiled_hybrid, which):
    pool = compiled_hybrid["pool"]
    text = compiled_hybrid[which].as_text()
    for name in ("k", "v", "state"):
        made = _made(text, pool[name].shape)
        ops = {op for op, _ in made}
        # (a pool of ONE layer that keeps K/V is written through a view
        # without the leading axis: what must not be there is a copy)
        assert not ops & {"copy", "copy-start", "copy-done", "transpose"}, (
            name, sorted(ops))
        # one layout from the argument to the result: the stored one
        assert {order for _, order in made} == {
            tuple(range(pool[name].ndim))[::-1]}, name
    # every pool array, K, V, state and tails, goes out in the buffer it
    # came in (the state's 192 columns are stored as 256)
    stats = compiled_hybrid[which].memory_analysis()
    padded_state = pool["state"].nbytes // 192 * 256
    assert stats.alias_size_in_bytes == (
        2 * pool["k"].nbytes + padded_state + pool["conv"].nbytes)
    # what is held beside them is a layer's gathered rows, not a pool
    assert stats.temp_size_in_bytes < 1.1 * pool["k"].nbytes


def test_the_hybrid_step_reads_k_and_v_in_the_pool_through_the_kernel(
        compiled_hybrid):
    """Thirty heads of 128 on one unpadded axis meet the rule
    (``ops/paged_decode.reads_in_place``): the full layer's one-token
    attention is the paged kernel (ISSUE 35), which takes the pool's own
    buffers, and no row is gathered out of them."""
    pool = compiled_hybrid["pool"]
    text = compiled_hybrid["step"].as_text()
    # one call a full layer (thirty heads' own 128 columns side by side on
    # one row since ISSUE 37, as the output projection takes them), handed
    # the step's K and V parameters as they are (no copy, no slice, no other
    # layout in between)
    calls = re.findall(
        r"%paged_decode[.\d]* = \(f32\[16,1,3840\]\S*, f32\[16,30,1\]\S*, "
        r"f32\[16,30,1\]\S*\) custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 1
    operands = [a.strip() for a in calls[0].split(",")]
    for name in operands[-2:]:
        assert re.search(
            r"%s = bf16\[1,8192,16,3840\]\{3,2,1,0:T\(8,128\)\(2,1\)\} "
            r"parameter\(" % re.escape(name), text), name
    # what the parent gathered, 16 rows x 512 blocks a layer and 1.0 GB a
    # gather, is not made in any spelling (in this pool of ONE layer the
    # flat spelling is the pool's own view, which only the column write
    # makes), and nothing of that size is held beside the pool
    assert _made(text, (16, 8192, 3840)) == []
    assert _made(text, (16, 512, 16, 3840)) == []
    assert {op for op, _ in _made(text, (8192, 16, 3840))} <= {
        "parameter", "bitcast", "scatter", "fusion"}
    stats = compiled_hybrid["step"].memory_analysis()
    assert stats.temp_size_in_bytes < pool["k"].nbytes / 16


@pytest.mark.parametrize("family, rows", [
    ("compiled", (SLOTS, MAX_LEN // 16, 16, 1664)),
    ("compiled_afmoe", (4, 512, 16, 4, 128)),
])
def test_a_family_the_rule_leaves_alone_keeps_its_gathers(request, family,
                                                          rows):
    """GPT-2 XL (heads of 64 on a padded axis) and Trinity (a per-head
    pool) do not meet the rule: their steps hold no paged kernel and
    gather their rows through the table as they did."""
    text = request.getfixturevalue(family)["step"].as_text()
    # (by the instruction's name: the text also lists the files of its
    # stack frames, a test file of that name among them)
    assert not re.search(r"%paged_decode[.\d]* = ", text)
    flat = (rows[0] * rows[1],) + rows[2:]
    assert _made(text, rows) or _made(text, flat)


def test_a_mid_chunk_hands_the_running_state_on_in_its_own_buffers(
        compiled_hybrid):
    stats = compiled_hybrid["mid"].memory_analysis()
    private = 1 * 8448 * 3840 * 2
    state = 30 * 96 * 256 * 4      # as the chip pads it
    assert stats.alias_size_in_bytes >= 2 * private + state
    text = compiled_hybrid["mid"].as_text()
    # the triangular systems are solved by the kernel (ISSUE 39), one call a
    # linear layer, not by the chip's 64-step inverse of a block of order
    # 64; the products around it are at full precision
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert len(re.findall(r"%delta_solve[.\d]* = f32\[1,30,4,64,288\]", text)) == 1
    assert "operand_precision={highest,highest}" in text


@pytest.fixture(scope="module")
def hybrid_scan(one_chip, for_the_chip, compiled_hybrid):
    """A MID chunk of 256 tokens over TWO linear-attention layers and a full
    one at Olmo-Hybrid-7B's widths, as lowered and as compiled for the chip
    (``compiled_hybrid`` has turned the kernels' interpreter off)."""
    from sparkdl_tpu.models.olmo_hybrid import (
        FULL,
        LINEAR,
        OlmoHybridConfig,
        OlmoHybridLMHeadModel,
    )

    cfg = OlmoHybridConfig(vocab_size=512, layer_types=(LINEAR, LINEAR, FULL),
                           dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: OlmoHybridLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=16, max_len=8192,
                              auto_start=False)
    try:
        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        private = jax.ShapeDtypeStruct(
            (1, 1, eng._wp, 3840), jnp.bfloat16, sharding=one_chip)
        rec = {name: jax.ShapeDtypeStruct(
            (2, 1) + a.shape[2:], a.dtype, sharding=one_chip)
            for name, a in eng._pool_kv.items() if name in ("state", "conv")}
        lowered = eng._chunk_mid_fn.lower(
            _on(one_chip, variables), private, private, ints(),
            ints(1, 256), 4096, ints(), rec)
        return {"lowered": lowered.as_text(),
                "compiled": lowered.compile().as_text()}
    finally:
        eng.close()


def test_two_linear_layers_solve_in_one_lowered_kernel(hybrid_scan):
    """Each linear layer's sub-chunks are solved by ``ops/delta_solve``'s
    kernel, and no program lowers it: the kernel is lowered once a process
    for each shape and kept as text (``jax.export``), which both layers of
    this program call as ONE function and which the other fixture's mid
    chunk of the same width took as it was (a kernel lowered in each program
    cost this one 0.35-0.8 s of every start of every chunk program, PERF.md
    section 6, PR 39). The chip's row-by-row inverse is gone."""
    from sparkdl_tpu.ops import delta_solve

    lowered, text = hybrid_scan["lowered"], hybrid_scan["compiled"]
    assert lowered.count("func.func private @call_exported__delta_solve") == 1
    assert lowered.count("call @call_exported__delta_solve") == 2
    assert lowered.count("tpu_custom_call") == 1
    # two shapes in both fixtures' programs, each lowered once: one
    # sub-chunk (the eight tokens the variables are shaped from) and the
    # four of a chunk of 256 (three programs, four layers)
    info = delta_solve._lowered_for_the_chip.cache_info()
    assert info.misses == 2 and info.hits >= 3
    assert "InvertDiagBlocksLowerTriangular" not in text
    calls = re.findall(
        r"%delta_solve[.\d]* = f32\[1,30,4,64,288\]\S* custom-call\(", text)
    assert len(calls) == 2


def test_the_benchmarks_reader_finds_every_operation_of_the_scan(hybrid_scan):
    """The device trace carries no scope, so the benchmark finds the
    chunkwise recurrence's operations by the SHAPES of their results
    (``benchmark/readers_olmo_hybrid.is_scan_op``, imported as it stands).
    Every fusion, custom call and loop of the compiled chunk that the
    program made under its ``gated_delta_scan`` scope is one the reader's
    rule takes, the kernel among them, but for the masks of a sub-chunk and
    a number a head and sub-chunk (nothing of a system's size, and none of
    them new): ``delta_scan_device_ms.hybrid`` reads the whole of the new
    path."""
    import json
    import os

    import benchmark
    from benchmark.readers_olmo_hybrid import is_scan_op

    # the cell's own configuration: the keys the reader takes its sizes from
    with open(os.path.join(os.path.dirname(benchmark.__file__), "configs",
                           "olmo-hybrid-7b-serve.json")) as f:
        hf = json.load(f)
    entry = hybrid_scan["compiled"].split("\nENTRY ", 1)[1]
    scoped = [ln.strip() for ln in entry.splitlines()
              if "gated_delta_scan" in ln
              and re.search(r" (fusion|custom-call|while)\(", ln)]
    assert len(scoped) > 10
    assert any(ln.startswith("%delta_solve") for ln in scoped)
    missed = [ln.split(", metadata=")[0] for ln in scoped
              if not is_scan_op(ln.split(", metadata=")[0], hf)]
    assert len(missed) < len(scoped) // 4
    for ln in missed:
        made = re.findall(r"\w+\[([\d,]*)\]", ln.split(" fusion(")[0])
        assert made and all(
            math.prod(int(d) for d in dims.split(",")) <= 64 * 64
            for dims in made), ln[:160]


# -- the mimo_v2_flash family at its published widths (ISSUE 36) ------------------

def _mimo_engine(monkeypatch_module):
    """A 32 x 16384 engine over a full dense layer, a window expert layer
    and a full expert layer at MiMo-V2-Flash's widths, 16 of 256 experts
    held, on abstract variables; and those variables."""
    from sparkdl_tpu.models.mimo_v2_flash import (
        FULL,
        WINDOW,
        MimoV2FlashConfig,
        MimoV2FlashLMHeadModel,
    )
    from sparkdl_tpu.ops import paged_decode
    from sparkdl_tpu.parallel import moe_dropless

    # the grouped product and the paged attention the CHIP runs (this
    # process's backend is the CPU)
    monkeypatch_module.setattr(moe_dropless, "auto_interpret", lambda: False)
    monkeypatch_module.setattr(paged_decode, "auto_interpret", lambda: False)
    cfg = MimoV2FlashConfig(
        vocab_size=512, hybrid_layer_pattern=(FULL, WINDOW, FULL),
        moe_layer_freq=(0, 1, 1), experts_held=16, dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: MimoV2FlashLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return ContinuousGPTEngine(cfg, variables, n_slots=32, max_len=16384,
                               auto_start=False), variables


def _mimo_step(eng, variables, one_chip):
    """The engine's decode step at 512 blocks a row, lowered."""
    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    slots = eng.n_slots
    return eng._paged_step_fn.lower(
        _on(one_chip, variables), _on(one_chip, eng._pool_kv),
        ints(slots, eng._mb), ints(slots), ints(slots), ints(slots),
        1, 512)


@pytest.fixture(scope="module")
def compiled_mimo(one_chip, for_the_chip, monkeypatch_module):
    """The decode step (512 blocks a row), a MID chunk (the rings
    go in and come out) and a FINAL chunk (it installs K/V into the slot's
    blocks and the rings into the slot's row) of a 32 x 16384 engine over a
    full dense layer, a window expert layer and a full expert layer at
    MiMo-V2-Flash's widths, 16 of 256 experts held, compiled for the chip:
    keys of 192 over values of 128, K on one axis of 768 and V on one of
    512, the window layer's last 128 columns a slot in a ring."""
    eng, variables = _mimo_engine(monkeypatch_module)
    max_len = 16384
    try:
        pool = eng._pool_kv

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        mb = max_len // 16
        private = {
            name: jax.ShapeDtypeStruct(
                (2, 1, eng._wp) + pool[name].shape[3:], jnp.bfloat16,
                sharding=one_chip) for name in ("k", "v")}
        rec = {name: jax.ShapeDtypeStruct(
            (1, 1) + a.shape[2:], a.dtype, sharding=one_chip)
            for name, a in pool.items() if name in ("win_k", "win_v")}
        head = (_on(one_chip, variables), _on(one_chip, pool))
        lowered = _mimo_step(eng, variables, one_chip)
        return {
            "pool": pool,
            "stored": {name: _device_layout(one_chip, a)
                       for name, a in pool.items()},
            "step": lowered.compile(),
            "step_lowered": lowered.as_text(),
            "mid": eng._chunk_mid_fn.lower(
                head[0], private["k"], private["v"], ints(), ints(1, 256),
                8192, ints(), rec).compile(),
            "final": eng._chunk_final_fn.lower(
                *head, private["k"], private["v"], ints(), ints(1, 256),
                ints(mb), 8192, ints(), rec, ints()).compile(),
        }
    finally:
        eng.close()


@pytest.fixture(scope="module")
def gathered_mimo_step(one_chip, for_the_chip):
    """The same engine's decode step as it was before the paged kernel took
    grouped heads of unequal size (ISSUE 37): the rule made to refuse, so
    that the full layers gather their rows at 512 blocks a row."""
    from sparkdl_tpu.ops import paged_decode

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_decode, "reads_in_place", lambda *a: False)
        eng, variables = _mimo_engine(mp)
        try:
            return _mimo_step(eng, variables, one_chip).compile()
        finally:
            eng.close()


def test_keys_of_192_and_values_of_128_each_lie_on_one_axis_row_major(
        one_chip, compiled_mimo):
    """4 K heads of 192 go on one axis of 768 (six lane tiles, no pad); V's
    4 heads of 128 join them on one of 512 (``kv_pool.kv_tails``): both
    pools are row-major, and so are the rings, a slot's 128 columns of 8
    heads together."""
    pool = compiled_mimo["pool"]
    assert pool["k"].shape == (2, 32768, 16, 768)
    assert pool["v"].shape == (2, 32768, 16, 512)
    assert pool["win_k"].shape == (1, 32, 128, 1536)
    assert pool["win_v"].shape == (1, 32, 128, 1024)
    for name in pool:
        assert compiled_mimo["stored"][name].major_to_minor == (0, 1, 2, 3)
    # a head of 192 kept apart would be padded to two lane tiles a head
    apart = jax.ShapeDtypeStruct((2, 1024, 16, 4, 192), jnp.bfloat16)
    text = jax.jit(lambda x: x).lower(_on(one_chip, apart)).compile().as_text()
    assert "T(4,128)" in text or "T(8,128)" in text


@pytest.mark.parametrize("which", ["step", "final"])
def test_neither_the_unequal_pool_nor_the_rings_are_copied_around_a_write(
        compiled_mimo, which):
    pool = compiled_mimo["pool"]
    text = compiled_mimo[which].as_text()
    for name in ("k", "v"):
        made = _made(text, pool[name].shape)
        ops = {op for op, _ in made}
        assert ops & {"scatter", "dynamic-update-slice"}, (name, sorted(ops))
        assert not ops & {"copy", "copy-start", "copy-done", "transpose"}, (
            name, sorted(ops))
        assert {order for _, order in made} == {(3, 2, 1, 0)}, name
    for name in ("win_k", "win_v"):
        # a ring is written where it lies (a scatter of one column a row in
        # the step, a slot's row in the last chunk); the chip may FETCH it
        # into faster memory to read it (``copy-start`` to ``S(1)``), which
        # is no copy around the write: the result is the argument's buffer
        made = _made(text, pool[name].shape) + _made(text,
                                                     pool[name].shape[1:])
        ops = {op for op, _ in made}
        assert not ops & {"copy", "transpose"}, (name, sorted(ops))
        assert {order[0] for _, order in made} == {len(order) - 1
                                                   for _, order in made}
    # every pool array, K, V and both rings, goes out in the buffer it came in
    stats = compiled_mimo[which].memory_analysis()
    assert stats.alias_size_in_bytes == sum(a.nbytes for a in pool.values())
    # what is held beside them is a chunk's scores (final: 64 heads x 256
    # queries x 8,192 keys in float32, 0.54 GB, and their exponentials), not
    # a pool; the step reads K and V where they lie and holds no gathered
    # row (0.67 GB a full layer until ISSUE 37: 0.78 GB of temporaries then,
    # 0.11 now, the query projection's kernel in the other order)
    assert stats.temp_size_in_bytes < {"step": 1 / 8, "final": 0.6}[
        which] * pool["k"].nbytes


def _layers_that_make_a_rings_scores(text):
    """The layers (``layers_<n>`` of the instruction's ``op_name``) whose
    instructions of the ENTRY computation, which are what a device trace
    holds an event for, make a result ``[slots, heads, window]`` = ``[32,
    64, 128]``: what ``benchmark/readers_mimo_v2_flash.is_ring_op`` books
    as a window layer's scores, whatever made them."""
    entry = text[text.index("\nENTRY "):]
    layers = []
    for line in entry.splitlines():
        made = line.split(" = ", 1)[-1]
        opcode = re.search(r"[\])}] ([a-z-]+)\(", made)
        if (opcode is None or opcode.group(1) == "get-tuple-element"
                or not re.search(r"[a-z0-9]+\[32,64,128\]",
                                 made[:opcode.start() + 1])):
            continue
        layers.append(re.search(r"op_name=\"[^\"]*/(layers_\d+)/",
                                line).group(1))
    return sorted(layers)


def test_the_mimo_step_reads_k_and_v_in_the_pool_through_the_grouped_kernel(
        compiled_mimo, gathered_mimo_step):
    """Keys of 192 on an axis of 768 over values of 128 on one of 512, 64
    query heads over 4 K/V heads, meet the rule since ISSUE 37: each full
    layer's one-token attention is the paged kernel, handed the pool's own
    buffers, and no row is gathered out of them."""
    text = compiled_mimo["step"].as_text()
    # one call a full layer: 16 query heads' 128 columns of each of 4 K/V
    # heads side by side, the running maximum and sum a head
    calls = re.findall(
        r"%paged_decode[.\d]* = \(f32\[32,16,512\]\S*, f32\[32,64,1\]\S*, "
        r"f32\[32,64,1\]\S*\) custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 2
    for call in calls:
        operands = [a.strip() for a in call.split(",")]
        for name, width in zip(operands[-2:], (768, 512)):
            assert re.search(
                r"%s = bf16\[2,32768,16,%d\]\{3,2,1,0:T\(8,128\)\(2,1\)\} "
                r"parameter\(" % (re.escape(name), width), text), name
    # what the step gathered until then, 32 rows x 512 blocks a full layer,
    # is made in no spelling, nor are heads of 192 split off a row
    for tail in (768, 512):
        for rows in ((32, 8192, tail), (32, 512, 16, tail),
                     (32 * 512, 16, tail)):
            assert _made(text, rows) == [], rows
    for apart in ((32, 8192, 4, 192), (32, 8192, 4, 128),
                  (32, 128, 8, 192), (32, 128, 8, 128)):
        assert _made(text, apart) == [], apart
    # the benchmark's reader finds a ring's scores by their shape, [slots,
    # heads, window] = [32, 64, 128], which is also [slots, heads, a value
    # head]: neither the kernel nor the join after it hands a result over in
    # that shape, so what the reader books is the window layer's alone (the
    # gathered step's full layers, 0 and 2, each made one such result: their
    # heads' own columns summed out of the merged axis)
    assert _layers_that_make_a_rings_scores(text) == ["layers_1"] * 2
    assert _layers_that_make_a_rings_scores(gathered_mimo_step.as_text()) == [
        "layers_0", "layers_1", "layers_1", "layers_2"]
    # and the step holds less beside the pool than the gathers did
    assert (compiled_mimo["step"].memory_analysis().temp_size_in_bytes
            < gathered_mimo_step.memory_analysis().temp_size_in_bytes / 4)
    # three products an expert layer over the 16 HELD experts: 32 rows x 8
    # pairs padded to row tiles of 128, whichever experts the pairs went to
    calls = re.findall(r"%gmm[.\d]* = bf16\[256,(\d+)\]", text)
    assert sorted(calls) == ["2048", "2048", "2048", "2048", "4096", "4096"]
    assert re.search(r"bf16\[16,4096,2048\]\S* parameter\(", text)


def test_a_mimo_step_the_rule_refuses_gathers_its_rows_as_stored(
        gathered_mimo_step):
    """Where the rule does not hold the full layers keep ``layer_rows`` and
    ``merged_sink_attention``: the rows come through the table on their
    merged axes, are never split into heads of 192 (a padded copy of every
    row), and no kernel is called."""
    text = gathered_mimo_step.as_text()
    for tail in (768, 512):
        gathered = (_made(text, (32, 8192, tail))
                    + _made(text, (32, 512, 16, tail))
                    + _made(text, (32 * 512, 16, tail)))
        assert gathered, tail
        assert not {op for op, _ in gathered} & {"copy", "copy-start"}, tail
    for apart in ((32, 8192, 4, 192), (32, 8192, 4, 128)):
        assert _made(text, apart) == [], apart
    assert not re.search(r"%paged_decode[.\d]* = ", text)


def test_a_mimo_mid_chunk_hands_the_rings_on_in_their_own_buffers(
        compiled_mimo):
    stats = compiled_mimo["mid"].memory_analysis()
    private = 2 * (16384 + 256) * (768 + 512) * 2
    rings = 128 * (1536 + 1024) * 2
    assert stats.alias_size_in_bytes >= private + rings


# -- the lfm2_moe family at its published widths (ISSUE 42) -------------------------

@pytest.fixture(scope="module")
def compiled_lfm2(one_chip, for_the_chip, monkeypatch_module):
    """``get(which)``: the decode step (256 blocks a row), a MID chunk (the
    tails go in and come out) or a FINAL chunk (it installs K/V into the
    slot's blocks and the tails into the slot's row) of the benchmark's 64 x
    4096 engine over ALL TEN layers of its cut at LFM2-24B-A2B's widths
    (every expert held), compiled for the chip when first asked for: 8 K/V
    heads of 64 on one axis of 512 in the two attention layers, the eight
    convolutions' last two inputs a slot."""
    from sparkdl_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLMHeadModel
    from sparkdl_tpu.ops import paged_decode
    from sparkdl_tpu.parallel import moe_dropless

    # the grouped product and the paged attention the CHIP runs (this
    # process's backend is the CPU)
    monkeypatch_module.setattr(moe_dropless, "auto_interpret", lambda: False)
    monkeypatch_module.setattr(paged_decode, "auto_interpret", lambda: False)
    full = Lfm2MoeConfig(dtype=jnp.bfloat16)
    cfg = dataclasses.replace(full, layer_types=full.layer_types[:10])
    variables = jax.eval_shape(
        lambda: Lfm2MoeLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=64, max_len=4096,
                              auto_start=False)
    pool, mb = eng._pool_kv, 4096 // 16

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    private = {name: jax.ShapeDtypeStruct(
        (2, 1, eng._wp) + pool[name].shape[3:], jnp.bfloat16,
        sharding=one_chip) for name in ("k", "v")}
    rec = {"conv": jax.ShapeDtypeStruct(
        (8, 1) + pool["conv"].shape[2:], jnp.bfloat16, sharding=one_chip)}
    head = (_on(one_chip, variables), _on(one_chip, pool))
    lower = {
        "step": lambda: eng._paged_step_fn.lower(
            *head, ints(64, mb), ints(64), ints(64), ints(64), 1, 256),
        "mid": lambda: eng._chunk_mid_fn.lower(
            head[0], private["k"], private["v"], ints(), ints(1, 256), 4096,
            ints(), rec),
        "final": lambda: eng._chunk_final_fn.lower(
            *head, private["k"], private["v"], ints(), ints(1, 256),
            ints(mb), 4096, ints(), rec, ints()),
    }
    done, lowered = {}, {}

    def get(which):
        if which not in done:
            lowered[which] = lower[which]()
            done[which] = lowered[which].compile()
        return done[which]

    get.pool, get.lowered = pool, lowered
    try:
        yield get
    finally:
        eng.close()


@pytest.mark.parametrize("which", ["step", "mid", "final"])
def test_neither_the_head_64_pool_nor_the_tails_are_held_twice(
        compiled_lfm2, which):
    """K and V ``bf16[2,16384,16,512]`` row-major and the tails
    ``bf16[8,64,2,2048]`` go out in the buffers they came in, in every
    program that writes them, and nothing the size of a pool is held beside
    them."""
    pool = compiled_lfm2.pool
    assert {k: a.shape for k, a in pool.items()} == {
        "k": (2, 16384, 16, 512), "v": (2, 16384, 16, 512),
        "conv": (8, 64, 2, 2048)}
    compiled = compiled_lfm2(which)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    if which == "mid":
        # the prompt's private cache and its running tails, not the pool
        private = 2 * 2 * (4096 + 256) * 512 * 2
        assert stats.alias_size_in_bytes >= private + 8 * 2 * 2048 * 2
        return
    for name in ("k", "v"):
        made = _made(text, pool[name].shape)
        ops = {op for op, _ in made}
        assert ops & {"scatter", "dynamic-update-slice"}, (name, sorted(ops))
        assert not ops & {"copy", "copy-start", "copy-done", "transpose"}, (
            name, sorted(ops))
        assert {order for _, order in made} == {(3, 2, 1, 0)}, name
    assert stats.alias_size_in_bytes == sum(a.nbytes for a in pool.values())
    # the tails (4.2 MB, all eight layers') are the one array the chip
    # re-lays for the step: slots in the sublanes while it works on them
    # (``{3,1,2,0}``), back to the stored order at the end, one copy each
    # way in fast memory; a chunk's last program writes one slot's row
    tails = _made(text, pool["conv"].shape)
    copies = [order for op, order in tails if op == "copy"]
    assert len(copies) <= 2, copies
    # what is held beside them: a step's logits and its experts' rows (the
    # paged kernel reads K and V in the pool since ISSUE 43: the gathered
    # rows of a layer, 64 x 4096 x 512 x 2 bytes = 0.27 GB for K and again
    # for V, are gone); a chunk's scores and logits
    assert stats.temp_size_in_bytes < {"step": 0.06e9, "final": 0.25e9}[which]


def test_the_lfm2_step_reads_k_and_v_in_the_pool_and_runs_the_grouped_matmul(
        compiled_lfm2):
    """What the cell will say of this path: 8 K/V heads of 64 on one axis of
    512 meet the rule since ISSUE 43 (V's AXIS is whole lane tiles, a value
    head need not be), so each attention layer's one-token attention is the
    paged kernel, handed the pool's own buffers, and no slot's rows are
    gathered at the bucket's depth; the experts' three products a layer are
    the grouped matmul kernel, at an expert width of 1,536 that its tiles
    of 512 divide since ISSUE 46 (``moe_dropless.gmm_tiling``)."""
    text = compiled_lfm2("step").as_text()
    # one call an attention layer: 4 query heads' 64 columns of each of 8
    # K/V heads side by side on a row of 512, the running maximum and sum a
    # head
    calls = re.findall(
        r"%paged_decode[.\d]* = \(f32\[64,4,512\]\S*, f32\[64,32,1\]\S*, "
        r"f32\[64,32,1\]\S*\) custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 2
    for call in calls:
        for name in [a.strip() for a in call.split(",")][-2:]:
            assert re.search(
                r"%s = bf16\[2,16384,16,512\]\{3,2,1,0:T\(8,128\)\(2,1\)\} "
                r"parameter\(" % re.escape(name), text), name
    # what the step gathered until then, 64 rows x 256 blocks a layer (0.27
    # GB for K and again for V), is made in no spelling
    for rows in ((64, 256, 16, 512), (64, 4096, 512), (64 * 256, 16, 512)):
        assert _made(text, rows) == [], rows
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 8 * 3
    # gate and up 2,048 x 1,536, down 1,536 x 2,048: no block of 1,024 x
    # 1,024, whose second column tile was half pad and whose second
    # contraction tile a masked remainder
    blocks = _gmm_kernel_blocks(compiled_lfm2.lowered["step"].as_text())
    assert sorted(blocks) == ["2048x512", "512x2048"]
    # both forms of the convolution carry their scope in the compiled text
    assert "short_conv_step" in text and "short_conv_chunk" not in text
    assert "short_conv_chunk" in compiled_lfm2("mid").as_text()


def test_the_benchmarks_reader_finds_the_convolutions_operations(
        compiled_lfm2):
    """``benchmark/readers_lfm2_moe.is_conv_op`` (imported as it stands) on
    the instructions of the step's ENTRY computation, which are what a
    device trace holds an event for: it takes the operations scoped
    ``short_conv_step`` that touch a tail, the eight splits, and nothing of
    the attention or the experts."""
    from benchmark import readers_lfm2_moe

    hf = {"hidden_size": 2048, "num_attention_heads": 32,
          "num_key_value_heads": 8, "num_hidden_layers": 10,
          "layer_types": ["conv", "conv"] + [
              "full_attention", "conv", "conv", "conv"] * 2,
          "num_dense_layers": 2, "conv_L_cache": 3,
          "intermediate_size": 11776, "moe_intermediate_size": 1536,
          "num_experts": 64, "num_experts_per_tok": 4, "vocab_size": 65536,
          "rope_parameters": {"rope_theta": 1000000}}
    text = compiled_lfm2("step").as_text()
    entry = text[text.index("\nENTRY "):]
    taken = [ln.strip() for ln in entry.splitlines()
             if " = " in ln and readers_lfm2_moe.is_conv_op(
                 ln.strip().split(", metadata=")[0], hf, 64)]
    assert len(taken) >= 8 * 2
    # the split comes out of each convolution's input projection as
    # ``bf16[64,1,6144]``, once a layer
    assert sum("6144]" in ln.split(" = ")[1].split(" ")[0]
               and " fusion(" in ln for ln in taken) == 8
    for ln in taken:
        assert "gmm" not in ln.split(" = ")[0]
        assert "self_attn" not in ln and "moe" not in ln, ln[:200]


# -- the glm_moe_dsa family at its published widths (ISSUE 44) ----------------------

@pytest.fixture(scope="module")
def compiled_glm(one_chip, for_the_chip, monkeypatch_module):
    """``get(which)``: the decode step (1,024 blocks a row, the table's whole
    width: 8 selections, which the rule ``sparse_attention.attends_in_place``
    takes), the same step as it was before the rule (ISSUE 45:
    ``step_gathered``, from a second engine built with the rule's constant
    at 1, so that no width is inside it), a MID chunk and a FINAL chunk (it
    installs both arrays into the slot's blocks) at 16,384 columns of the
    benchmark's 32 x 16384 engine over ALL FIVE layers of its cut at
    GLM-5.2's widths (16 of 256 experts held), compiled for the chip when
    first asked for. The engines here are built over a pool of 64 blocks
    (nothing of the published pool's 3.6 GB is allocated on this CPU); the
    programs are lowered for the benchmark's 32,768."""
    from sparkdl_tpu.models.glm_moe_dsa import (
        GlmMoeDsaConfig,
        GlmMoeDsaLMHeadModel,
    )
    from sparkdl_tpu.ops import paged_decode, sparse_attention
    from sparkdl_tpu.parallel import moe_dropless

    monkeypatch_module.setattr(moe_dropless, "auto_interpret", lambda: False)
    monkeypatch_module.setattr(paged_decode, "auto_interpret", lambda: False)
    cfg = GlmMoeDsaConfig(
        vocab_size=19456,
        indexer_types=("full", "shared", "shared", "shared", "full"),
        mlp_layer_types=("dense",) + ("sparse",) * 4, experts_held=16,
        dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: GlmMoeDsaLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=32, max_len=16384,
                              kv_blocks=64, auto_start=False)
    mb, blocks = 16384 // 16, 32 * 1024

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    pool = {name: jax.ShapeDtypeStruct(
        (a.shape[0], blocks) + a.shape[2:], a.dtype, sharding=one_chip)
        for name, a in eng._pool_kv.items()}
    private = [jax.ShapeDtypeStruct(
        (pool[name].shape[0], 1, eng._wp) + pool[name].shape[3:],
        jnp.bfloat16, sharding=one_chip) for name in ("latent", "index_k")]
    head = (_on(one_chip, variables), pool)

    def step(engine):
        return engine._paged_step_fn.lower(
            *head, ints(32, mb), ints(32), ints(32), ints(32), 1, mb)

    def step_gathered():
        # (another engine: the first one's step is traced already)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(sparse_attention, "IN_PLACE_SELECTIONS", 1)
            other = ContinuousGPTEngine(cfg, variables, n_slots=32,
                                        max_len=16384, kv_blocks=64,
                                        auto_start=False)
            try:
                return step(other)
            finally:
                other.close()

    lower = {
        "step": lambda: step(eng),
        "step_gathered": step_gathered,
        "mid": lambda: eng._chunk_mid_fn.lower(
            head[0], *private, ints(), ints(1, 256), 16384),
        "final": lambda: eng._chunk_final_fn.lower(
            *head, *private, ints(), ints(1, 256), ints(mb), 16384),
    }
    done, lowered = {}, {}

    def get(which):
        if which not in done:
            lowered[which] = lower[which]()
            done[which] = lowered[which].compile()
        return done[which]

    get.pool, get.lowered = pool, lowered
    try:
        yield get
    finally:
        eng.close()


@pytest.mark.parametrize("which", ["step", "mid", "final"])
def test_the_latent_pool_and_the_indexers_keys_are_written_where_they_lie(
        compiled_glm, which):
    """``latent`` ``bf16[5,32768,16,640]`` and ``index_k``
    ``bf16[2,32768,16,128]``, each over its OWN layers, lie row-major and go
    out in the buffers they came in, in every program that writes them;
    what is held beside them fits the chip with the 11.4 GB of weights and
    pool."""
    pool = compiled_glm.pool
    assert {k: a.shape for k, a in pool.items()} == {
        "latent": (5, 32768, 16, 640), "index_k": (2, 32768, 16, 128)}
    compiled = compiled_glm(which)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    pool_bytes = sum(math.prod(a.shape) * 2 for a in pool.values())
    assert pool_bytes == 3_623_878_656
    if which == "mid":
        # the prompt's private rows of both arrays, not the pool
        private = (5 * 640 + 2 * 128) * (16384 + 256) * 2
        assert stats.alias_size_in_bytes >= private
        assert stats.temp_size_in_bytes < 0.8e9
        return
    for name in ("latent", "index_k"):
        made = _made(text, pool[name].shape)
        ops = {op for op, _ in made}
        assert ops & {"scatter", "dynamic-update-slice", "fusion"}, (
            name, sorted(ops))
        assert not ops & {"copy", "copy-start", "copy-done", "transpose"}, (
            name, sorted(ops))
        assert {order for _, order in made} == {(3, 2, 1, 0)}, name
    assert stats.alias_size_in_bytes >= pool_bytes
    # beside them: a step's gathered keys (32 x 16384 x 128), its selected
    # columns of a layer (32 x 2048 x 640) and scores; a chunk's per-head K
    # and V of 16 heads and their scores over 16 k columns
    assert stats.temp_size_in_bytes < {"step": 0.8e9, "final": 1.3e9}[which]
    assert (stats.argument_size_in_bytes + stats.temp_size_in_bytes
            < 13.0e9)


def test_the_glm_step_attends_its_rows_live_blocks_in_the_pool_under_the_mask(
        compiled_glm):
    """A table of 16,384 columns is 8 selections, inside the rule: each of
    the five layers calls the paged kernel ONCE over the ``latent`` array
    alone (the step's own parameter, layer by layer: no copy, no slice, no
    V), under a bias ``f32[32,1,16384]`` from the selection's mask; no
    layer reads 32 x 2,048 columns one by one, nothing sorts a row's
    scores (the selection is the bisection's 32 passes), and what the step
    holds beside the pool is less than the gathers'."""
    text = compiled_glm("step").as_text()
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(
        r"%paged_decode[.\d]* = \(f32\[32,64,640\]\S*, f32\[32,64,1\]\S*, "
        r"f32\[32,64,1\]\S*\) custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{(.*?)\}\}", text)
    assert len(calls) == 5
    for operands, layouts in calls:
        operands = [a.strip() for a in operands.split(",")]
        # table, depths, the absorbed query, the bias, the pool: one array
        assert len(operands) == 5
        assert re.search(
            r"%s = bf16\[5,32768,16,640\]\{3,2,1,0:T\(8,128\)\(2,1\)\} "
            r"parameter\(" % re.escape(operands[-1]), text), operands[-1]
        assert "f32[32,1,16384]" in layouts and "bf16[32,64,640]" in layouts
    assert not re.search(r"bf16\[65536,640\]", text)
    assert not re.search(r"s32\[65536\]", entry)
    for rows in ((32, 16384, 640), (32, 1024, 16, 640), (32768, 16, 640),
                 (32, 2048, 640)):
        assert _made(text, rows) == [], rows
    assert not re.search(r"f32\[32,16384\]\S*, s32\[32,16384\]\S*\) sort\(",
                         text)
    # the keys still come through the table, twice
    assert len(_made(text, (32, 16384, 128))) >= 2
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 4 * 3
    # widths of 6,144 and 2,048 keep the kernel blocks they had (ISSUE 46)
    assert _gmm_kernel_blocks(compiled_glm.lowered["step"].as_text()) == [
        "1024x1024"] * 2
    for scope in ("dsa_indexer", "dsa_select", "dsa_attend_in_place"):
        assert scope in text, scope
    for scope in ("dsa_selected_read", "dsa_absorbed_attention",
                  "dsa_expanded_attention"):
        assert scope not in text, scope
    assert (compiled_glm("step").memory_analysis().temp_size_in_bytes
            <= compiled_glm("step_gathered").memory_analysis()
            .temp_size_in_bytes)


def test_a_glm_step_the_rule_refuses_reads_selected_columns_alone_and_sorts(
        compiled_glm):
    """What the step was until the rule, and is past it: each of the five
    layers reads 32 x 2,048 SELECTED columns of 640 one by one and never
    the rows' 16,384 (a whole row's ``latent`` through the table would be
    ``[32, 16384, 640]``: 0.67 GB a layer); the two ``full`` layers read
    their keys through the table and sort a row's scores once; the experts'
    three products a layer are the grouped matmul kernel; no paged
    kernel."""
    text = compiled_glm("step_gathered").as_text()
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r"%paged_decode[.\d]* = ", text)
    assert len(re.findall(r"= bf16\[65536,640\]\S* fusion\(", entry)) == 5
    for rows in ((32, 16384, 640), (32, 1024, 16, 640), (32768, 16, 640)):
        assert _made(text, rows) == [], rows
    assert len(re.findall(
        r"= \(f32\[32,16384\]\S*, s32\[32,16384\]\S*\) sort\(", entry)) == 2
    assert len(_made(text, (32, 16384, 128))) >= 2
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 4 * 3
    # the stages carry their scopes in the compiled text
    for scope in ("dsa_indexer", "dsa_select", "dsa_selected_read",
                  "dsa_absorbed_attention"):
        assert scope in text, scope
    assert "dsa_expanded_attention" not in text
    assert "dsa_attend_in_place" not in text
    mid = compiled_glm("mid").as_text()
    assert "dsa_expanded_attention" in mid and "dsa_indexer" in mid
    # a chunk's selection is a count a bit, not a sort of 256 x 16,384
    assert not re.search(r"f32\[256,16384\]\S*, s32\[256,16384\]\S*\) sort\(",
                         mid)


def _entry_instructions(compiled):
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    return [ln.strip().split(", metadata=")[0] for ln in entry.splitlines()
            if " = " in ln and " parameter(" not in ln]


def test_the_benchmarks_readers_find_the_kernel_and_the_masks_operations(
        compiled_glm):
    """``benchmark/readers_glm_moe_dsa.is_sparse_attn_op`` and
    ``is_indexer_op`` (imported as they stand) on the ENTRY instructions of
    the step that attends in place: the first takes each layer's kernel
    call (by its result ``f32[32,64,640]``), the absorbed query and the
    mix, and nothing of 2,048; the second the keys' gather, the scores, the
    bisection's passes and the bias made of the mask; neither takes the
    other's, the experts', the projections' or the logits."""
    from benchmark import manifest as mf
    from benchmark import readers_glm_moe_dsa as readers_g
    from benchmark.runners import serve_glm_moe_dsa

    hf = serve_glm_moe_dsa.hf_config(
        mf.resolve_cell("glm52-sparse-agent-backlog").config)
    lines = _entry_instructions(compiled_glm("step"))
    index = [ln for ln in lines if readers_g.is_indexer_op(ln, hf, 32)]
    attend = [ln for ln in lines if readers_g.is_sparse_attn_op(ln, hf, 32)]
    assert not set(index) & set(attend)
    kernels = [ln for ln in lines if ln.startswith("%paged_decode")]
    assert len(kernels) == 5 and all(ln in attend for ln in kernels)
    assert not any("2048" in ln.split(" = ")[1].split(" ")[0]
                   for ln in attend)
    assert sum("f32[32,64,640]" in ln or "bf16[32,64,640]" in ln
               for ln in attend) >= 10
    assert not any(" sort(" in ln or " while(" in ln for ln in index + attend)
    assert sum("bf16[32768,16,128]" in ln and "index_k" in ln
               for ln in index) == 2
    # a row's scores as the bisection orders them, and the bias made of
    # the mask, each once a ``full`` layer
    for made in ("u32[32,16384]", "f32[32,1,16384]"):
        assert sum(ln.split(" = ")[1].startswith(made) and " fusion(" in ln
                   for ln in index) == 2, made
    for ln in index + attend:
        assert "gmm" not in ln.split(" = ")[0]
        assert "19456" not in ln.split(" = ")[1].split(" ")[0], ln[:200]
        assert "[32,16384]{1,0:T(8,128)(2,1)" not in ln, ln[:200]   # q itself


def test_the_benchmarks_readers_find_the_two_stages_operations(compiled_glm):
    """``benchmark/readers_glm_moe_dsa.is_indexer_op`` and
    ``is_sparse_attn_op`` (imported as they stand) on the instructions of
    the GATHERED step's ENTRY computation, which are what a device trace
    holds an event for: the first takes the keys' gather, the scores and the sort,
    the second the selected read, the scores over 2,048 and the mix; neither
    takes the other's, the experts', the projections' or the logits."""
    from benchmark import manifest as mf
    from benchmark import readers_glm_moe_dsa as readers_g
    from benchmark.runners import serve_glm_moe_dsa

    hf = serve_glm_moe_dsa.hf_config(
        mf.resolve_cell("glm52-sparse-agent-backlog").config)
    lines = _entry_instructions(compiled_glm("step_gathered"))
    index = [ln for ln in lines if readers_g.is_indexer_op(ln, hf, 32)]
    attend = [ln for ln in lines if readers_g.is_sparse_attn_op(ln, hf, 32)]
    assert not set(index) & set(attend)
    assert sum(" sort(" in ln for ln in index) == 2
    # a loop's event spans its body's, which have their own: leaves only
    assert not any(" while(" in ln for ln in index + attend)
    # the keys' gather, whose first axis is slots x blocks and not slots
    assert sum("bf16[32768,16,128]" in ln and "index_k" in ln
               for ln in index) == 2
    assert sum("f32[32,16384]" in ln and " fusion(" in ln
               for ln in index) >= 2
    assert sum("bf16[65536,640]" in ln for ln in attend) == 5
    assert sum("f32[32,64,2048]" in ln and " fusion(" in ln
               for ln in attend) == 5
    assert sum("f32[32,64,640]" in ln for ln in attend) == 5
    for ln in index + attend:
        assert "gmm" not in ln.split(" = ")[0]
        assert "19456" not in ln.split(" = ")[1].split(" ")[0], ln[:200]
        assert "[32,16384]{1,0:T(8,128)(2,1)" not in ln, ln[:200]   # q itself


# -- the families that ran the paged kernel before it took one array (ISSUE 45) -----

#: sha256 (first 16 hex digits) of the per-slot step's LOWERED text at each
#: fixture's shapes, read at the PARENT of the PR that gave
#: ``ops/paged_decode.py`` its one-array form (this installation's jax): the
#: text as ``Lowered.as_text()`` gives it, each Mosaic kernel's body decoded
#: and printed without its locations (a body holds the line numbers of the
#: kernel's source, which moved)
PARENT_STEP_TEXT = {
    "olmo_hybrid": "b923a07e35d9284b",
    "mimo_v2_flash": "8a128b807a1eb698",
    # moved by ISSUE 46 (``e8950da616acd302`` until then, which the rule
    # ``min(1024, dim)`` still lowers to): the two ``gmm`` bodies' blocks,
    # 2,048 x 512 and 512 x 2,048 where both were 1,024 x 1,024, with them
    # the down product's masked remainder gone, and the bytes the two
    # calls' cost estimates count; nothing else. The other two families are
    # the same PR's control: unedited
    "lfm2_moe": "cf5439ebf765a5bd",
}
_QUOTE = r'(?:\\22|\\?")'
_BODY = re.compile(f"({_QUOTE}body{_QUOTE}: *{_QUOTE})([A-Za-z0-9+/=]+)"
                   f"({_QUOTE})")


def _without_locations(lowered_text):
    """The lowered text with every Mosaic kernel's serialized body replaced
    by its operations as text, locations dropped; and how many there were."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def plain(match):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            return (match.group(1)
                    + module.operation.get_asm(enable_debug_info=False)
                    + match.group(3))

    return _BODY.subn(plain, lowered_text)


@pytest.mark.parametrize("family, fixture, kernels", [
    ("olmo_hybrid", "compiled_hybrid", 1),
    ("mimo_v2_flash", "compiled_mimo", 2),
    ("lfm2_moe", "compiled_lfm2", 2),
])
def test_the_steps_that_ran_the_kernel_lower_to_the_parents_text(
        request, family, fixture, kernels):
    """Olmo-Hybrid's, MiMo-V2-Flash's and LFM2's steps call
    ``paged_decode_partial`` with K and V, no bias and no scale: the
    kernel's three new parameters are resolved while it is traced, and the
    step programs are the parent's, operation for operation."""
    import hashlib

    held = request.getfixturevalue(fixture)
    if callable(held):
        held("step")
        text = held.lowered["step"].as_text()
    else:
        text = held["step_lowered"]
    plain, bodies = _without_locations(text)
    assert bodies >= kernels
    assert plain.count("module @paged_decode") == kernels
    assert (hashlib.sha256(plain.encode()).hexdigest()[:16]
            == PARENT_STEP_TEXT[family])
