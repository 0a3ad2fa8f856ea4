"""Train-engine tests on the 8-virtual-device CPU mesh (parity with
areal/tests/test_train_engine.py's mock-input pattern, :21-48)."""

import dataclasses
import types

import numpy as np
import pytest

import jax

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import (
    MicroBatchSpec,
    OptimizerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, SaveLoadMeta
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.sft.lm_engine import (
    JaxLMEngine,
    compute_packed_sft_loss,
    sft_loss_weight,
)
from areal_tpu.models.qwen2 import ModelConfig
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.ops.flash_attention import block_liveness
from areal_tpu.utils.data import pad_sequences_to_tensors, zigzag_indices
from tests.test_flash_attention import brute_force_liveness, random_packing

TINY_MODEL = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)


def mock_batch(n=4, lens=(9, 13, 7, 11), vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    seqs = []
    for i in range(n):
        L = lens[i % len(lens)]
        ids = rng.randint(1, vocab, (L,))
        loss_mask = np.zeros(L, dtype=np.int32)
        loss_mask[L // 2 :] = 1  # "answer" half
        seqs.append(dict(input_ids=ids, loss_mask=loss_mask))
    return pad_sequences_to_tensors(seqs)


@pytest.fixture(scope="module")
def engine(cpu_devices):
    cfg = TrainEngineConfig(
        experiment_name="test",
        trial_name="t",
        path="",
        init_from_scratch=True,
        dtype="float32",
        mb_spec=MicroBatchSpec(max_tokens_per_mb=64),
        optimizer=OptimizerConfig(
            lr=5e-3, warmup_steps_proportion=0.0, lr_scheduler_type="constant",
            gradient_clipping=1.0,
        ),
        gradient_checkpointing=False,
    )
    eng = JaxLMEngine(cfg)
    eng.model_config = TINY_MODEL
    eng.create_process_group(
        ParallelStrategy(
            data_parallel_size=2, tensor_parallel_size=2, context_parallel_size=2
        )
    )
    eng.initialize(None, FinetuneSpec(1, 128, 4))
    return eng


@pytest.mark.slow
def test_sft_overfit_loss_decreases(engine):
    batch = mock_batch()
    losses = [engine.train_lm(batch)["loss"] for _ in range(12)]
    assert losses[-1] < losses[0] * 0.7, losses
    assert np.isfinite(losses).all()


@pytest.mark.slow
def test_eval_batch(engine):
    batch = mock_batch(seed=3)
    loss = engine.evaluate_lm(batch)
    assert np.isfinite(loss)


@pytest.mark.slow
def test_forward_reorders_to_input_order(engine):
    batch = mock_batch()
    lens = batch["attention_mask"].sum(1).astype(int)

    def post_hook(logits, mb):
        return logits.argmax(-1)

    out = engine.forward(batch, post_hook=post_hook, aggregate_fn=list)
    assert len(out) == 4
    for i, o in enumerate(out):
        assert o.shape[0] == lens[i], (i, o.shape, lens)


@pytest.mark.slow
def test_train_stats_contract(engine):
    stats = engine.train_lm(mock_batch(seed=5))
    for key in ("loss", "grad_norm", "lr", "n_mbs", "update_steps"):
        assert key in stats
    assert stats["grad_norm"] >= 0


@pytest.mark.slow
def test_save_load_roundtrip(engine, tmp_path):
    batch = mock_batch(seed=7)
    loss_before = engine.evaluate_lm(batch)
    engine.save(SaveLoadMeta(path=str(tmp_path / "ckpt"), with_optim=True))
    # perturb weights by training, then restore
    for _ in range(3):
        engine.train_lm(batch)
    engine.load(SaveLoadMeta(path=str(tmp_path / "ckpt"), with_optim=True))
    loss_after = engine.evaluate_lm(batch)
    assert abs(loss_before - loss_after) < 1e-4


@pytest.mark.parametrize(
    "attn_impl,dp,zigzag",
    [("flash", 1, False), ("ring", 4, False), ("ring", 4, True), ("dense", 1, False)],
    ids=["one_chip", "ring4", "ring4_zigzag", "dense_reads_as_flash"],
)
def test_attn_block_pcts_are_the_numpy_counts(cpu_devices, attn_impl, dp, zigzag):
    """`train_batch`'s two counters: the flash kernels' liveness rule and
    their work list on the host's segment ids, in the layout the model hands
    the kernels. Live against a brute force count over `_mask_for`
    (contiguous layouts: exact); walked against the span from each outer
    block's first to its last live partner, call by call (a ring step is a
    call), twice by query block (`%flash_fwd`, `%flash_dq`) and once by key
    block (`%flash_dkv`): the live count where no run has a hole."""
    mesh = mesh_lib.build_mesh(
        ParallelStrategy(data_parallel_size=dp), devices=cpu_devices[:dp]
    )
    cfg = dataclasses.replace(TINY_MODEL, attn_impl=attn_impl, cp_zigzag=zigzag)
    fake = types.SimpleNamespace(model_config=cfg, mesh=mesh)
    mbs, live, visits, walked = [], 0, 0, 0

    def spans(table):
        on = [np.flatnonzero(row) for row in table]
        return sum(int(x[-1] - x[0] + 1) for x in on if len(x))

    for seed, total in enumerate((8192, 4096, 2560)):
        seg = random_packing(total, seed, mean_len=400, pad=300)
        seg[seg < 0] = seg.max() + 1  # the packer's pad tail: a segment of its own
        cu = np.r_[0, np.flatnonzero(np.diff(seg)) + 1, total]
        mbs.append(dict(cu_seqlens=cu))
        pos = np.arange(total, dtype=np.int32)
        if zigzag:
            perm = zigzag_indices(total, dp)
            seg, pos = seg[perm], pos[perm]
        Tl = total // dp
        block = min(512, Tl)
        # each shard padded to whole blocks, as the kernels' wrappers pad it
        shard = lambda x, i, fill: np.pad(  # noqa: E731
            x[i * Tl:(i + 1) * Tl], (0, -Tl % block), constant_values=fill)
        for qi in range(dp):
            for ki in range(dp):
                brute = brute_force_liveness(
                    shard(seg, qi, -1), shard(seg, ki, -1), shard(pos, qi, 0),
                    shard(pos, ki, 0), block, block)
                live, visits = live + int(brute.sum()), visits + brute.size
                kept = block_liveness(
                    shard(seg, qi, -1), shard(seg, ki, -1), shard(pos, qi, 0),
                    shard(pos, ki, 0), block, block)
                walked += 2 * spans(kept) + spans(kept.T)
    pct, walked_pct = JaxTrainEngine._attn_block_pcts(fake, mbs)
    assert walked_pct == pytest.approx(100.0 * walked / (3 * visits))
    if zigzag:
        assert 100.0 * live / visits <= pct <= walked_pct < 40.0
    else:
        assert pct == pytest.approx(100.0 * live / visits) == walked_pct
        assert 5.0 < pct < 40.0


def test_loss_weight_counts_answer_tokens():
    batch = mock_batch(n=2, lens=(8, 8))
    from areal_tpu.utils.data import pack_tensor_dict

    packed = pack_tensor_dict(batch)
    from areal_tpu.models.qwen2 import segment_ids_from_cu_seqlens

    packed["segment_ids"] = segment_ids_from_cu_seqlens(
        np.asarray(packed["cu_seqlens"]), int(packed["cu_seqlens"][-1])
    )
    w = sft_loss_weight(packed)
    # each 8-token seq trains 4 answer labels (positions 3..6 predict 4..7)
    assert w == 8.0
