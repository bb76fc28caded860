"""BERT oracle tests (SURVEY.md §4 pattern): the Flax encoder with
converted HF weights must match the torch forward on the same batch; the
ring-attention variant must match the full-attention variant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.bert import (
    BertConfig,
    BertForSequenceClassification,
    BertModel,
    load_hf_bert,
)
from sparkdl_tpu.runtime.mesh import MeshSpec

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _tiny_hf(num_labels=None):
    hf_cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    )
    torch.manual_seed(0)
    if num_labels is None:
        return transformers.BertModel(hf_cfg).eval()
    hf_cfg.num_labels = num_labels
    return transformers.BertForSequenceClassification(hf_cfg).eval()


def _batch(rng, b=3, l=16, vocab=128):
    ids = rng.integers(0, vocab, (b, l))
    mask = np.ones((b, l), np.int32)
    mask[0, l // 2:] = 0  # one padded row
    return ids.astype(np.int32), mask


def test_bert_matches_hf_forward():
    hf = _tiny_hf()
    cfg, variables = load_hf_bert(hf)
    rng = np.random.default_rng(0)
    ids, mask = _batch(rng)

    with torch.no_grad():
        want = hf(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
        )
    model = BertModel(cfg)
    got_seq, got_pooled = model.apply(
        variables, jnp.asarray(ids), jnp.asarray(mask)
    )
    # Padded positions differ (HF still computes them attending to valid
    # keys; we do too) — compare everything.
    np.testing.assert_allclose(
        np.asarray(got_seq), want.last_hidden_state.numpy(), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(got_pooled), want.pooler_output.numpy(), atol=2e-5
    )


def test_bert_classifier_matches_hf():
    hf = _tiny_hf(num_labels=4)
    cfg, variables = load_hf_bert(hf)
    rng = np.random.default_rng(1)
    ids, mask = _batch(rng)
    with torch.no_grad():
        want = hf(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
        ).logits.numpy()
    model = BertForSequenceClassification(cfg, num_labels=4)
    got = model.apply(variables, jnp.asarray(ids), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_ring_attention_bert_matches_full():
    """Same weights, attn_impl='ring' under an sp=4 mesh == attn_impl='full'."""
    hf = _tiny_hf()
    cfg, variables = load_hf_bert(hf)
    rng = np.random.default_rng(2)
    ids, mask = _batch(rng, b=2, l=32)

    full = BertModel(cfg).apply(variables, jnp.asarray(ids), jnp.asarray(mask))[0]

    mesh = MeshSpec(dp=2, sp=4).build()
    ring_cfg = BertConfig(**{**cfg.__dict__, "attn_impl": "ring"})
    model = BertModel(ring_cfg)

    from jax.sharding import PartitionSpec as P

    def fwd(vars_, ids_, mask_):
        # Sequence dim sharded over sp inside shard_map; embeddings need
        # global position ids, so compute them outside and shard.
        b, l = ids_.shape
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))

        def local(ids_l, mask_l, pos_l):
            return model.apply(
                vars_, ids_l, mask_l, position_ids=pos_l
            )[0]

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P("dp", "sp"), P("dp", "sp"), P("dp", "sp")),
            out_specs=P("dp", "sp"),
            check_vma=False,
        )(ids_, mask_, pos)

    got = fwd(variables, jnp.asarray(ids), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(full), atol=3e-5)
