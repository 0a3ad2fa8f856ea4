"""Saver / Evaluator / RecoverHandler: freq gates, checkpoint round-trips,
and full train-state recovery (parity: areal/utils/{saver,evaluator,recover}.py).
"""

import os

import numpy as np
import pytest

import jax

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import (
    EvaluatorConfig,
    MicroBatchSpec,
    OptimizerConfig,
    RecoverConfig,
    SaverConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, StepInfo
from areal_tpu.dataset import SimpleDataLoader
from areal_tpu.engine.sft.lm_engine import JaxLMEngine
from areal_tpu.models.qwen2 import ModelConfig
from areal_tpu.utils.data import pad_sequences_to_tensors
from areal_tpu.utils.evaluator import Evaluator
from areal_tpu.utils.recover import (
    RecoverHandler,
    check_if_auto_recover,
    discard_recover_state,
    get_metrics,
    recover_root,
    reset_metrics,
    verify_step_dir,
)
from areal_tpu.utils.saver import Saver

TINY = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)

FT = FinetuneSpec(total_train_epochs=2, dataset_size=16, train_batch_size=4)


def _make_engine(cpu_devices):
    cfg = TrainEngineConfig(
        experiment_name="rec",
        trial_name="t",
        path="",
        init_from_scratch=True,
        dtype="float32",
        mb_spec=MicroBatchSpec(max_tokens_per_mb=128),
        optimizer=OptimizerConfig(
            lr=1e-2,
            warmup_steps_proportion=0.0,
            lr_scheduler_type="constant",
            gradient_clipping=1.0,
        ),
        gradient_checkpointing=False,
    )
    eng = JaxLMEngine(cfg)
    eng.model_config = TINY
    eng.create_process_group(
        ParallelStrategy(data_parallel_size=4, tensor_parallel_size=2)
    )
    eng.initialize(None, FT)
    return eng


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    seqs = []
    for L in (9, 13, 7, 11):
        ids = rng.randint(1, 64, (L,))
        mask = np.zeros(L, dtype=np.int32)
        mask[L // 2 :] = 1
        seqs.append(dict(input_ids=ids, loss_mask=mask))
    return pad_sequences_to_tensors(seqs)


def test_saver_freq_gate(tmp_path, cpu_devices):
    cfg = SaverConfig(
        experiment_name="rec", trial_name="t", fileroot=str(tmp_path), freq_steps=2
    )
    eng = _make_engine(cpu_devices)
    saver = Saver(cfg, FT)
    p0 = saver.save(eng, epoch=0, step=0, global_step=0)
    assert p0 is None  # gate not reached yet
    p1 = saver.save(eng, epoch=0, step=1, global_step=1)
    assert p1 is not None and os.path.exists(
        os.path.join(p1, "model.safetensors")
    )
    assert "epoch0epochstep1globalstep1" in p1
    eng.destroy()


def test_evaluator_freq_gate():
    ev = Evaluator(
        EvaluatorConfig(experiment_name="rec", trial_name="t", freq_steps=3), FT
    )
    ran = [ev.evaluate(lambda: None, 0, s, s) for s in range(6)]
    assert sum(ran) == 2


def test_recover_roundtrip(tmp_path, cpu_devices):
    rcfg = RecoverConfig(
        experiment_name="rec",
        trial_name="t",
        fileroot=str(tmp_path),
        mode="auto",
        freq_steps=1,
    )
    assert not check_if_auto_recover(rcfg)

    eng = _make_engine(cpu_devices)
    dl = SimpleDataLoader(list(range(16)), batch_size=4, seed=3)
    it = iter(dl)
    next(it)
    next(it)  # advance 2 batches

    # train 3 steps so moments are nontrivial
    for s in range(3):
        eng.train_lm(_batch(s))
    eng.set_version(3)

    saver = Saver(
        SaverConfig(
            experiment_name="rec", trial_name="t", fileroot=str(tmp_path), freq_steps=2
        ),
        FT,
    )
    saver.freq_ctl.check(steps=1)  # advance gate state to something nonzero
    handler = RecoverHandler(rcfg, FT)
    step_info = StepInfo(epoch=0, epoch_step=2, global_step=2, steps_per_epoch=4)
    root = handler.dump(eng, step_info, saver=saver, dataloader=dl)
    assert root is not None
    assert check_if_auto_recover(rcfg)
    params_before = jax.tree.leaves(eng.params)
    opt_before = jax.tree.leaves(eng.opt_state)
    eng.destroy()

    # fresh engine; load everything back
    eng2 = _make_engine(cpu_devices)
    saver2 = Saver(
        SaverConfig(
            experiment_name="rec", trial_name="t", fileroot=str(tmp_path), freq_steps=2
        ),
        FT,
    )
    dl2 = SimpleDataLoader(list(range(16)), batch_size=4, seed=3)
    handler2 = RecoverHandler(rcfg, FT)
    info = handler2.load(eng2, saver=saver2, dataloader=dl2)
    assert info is not None
    assert info.last_step_info.global_step == 2
    assert eng2.get_version() == 3
    assert saver2.state_dict() == saver.state_dict()
    assert dl2.state_dict() == dl.state_dict()
    for a, b in zip(params_before, jax.tree.leaves(eng2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(opt_before, jax.tree.leaves(eng2.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training must continue identically from the restored state
    s1 = eng2.train_lm(_batch(99))
    assert np.isfinite(s1["loss"])
    eng2.destroy()

    discard_recover_state(rcfg)
    assert not check_if_auto_recover(rcfg)


def test_orbax_sharded_checkpoint_preserves_shardings(tmp_path, cpu_devices):
    """The recover format is orbax: each restored leaf comes back already
    laid out on the engine's NamedShardings (no host-gathered pickle)."""
    from areal_tpu.api.io_struct import SaveLoadMeta

    eng = _make_engine(cpu_devices)
    eng.train_lm(_batch(0))
    eng.set_version(5)
    path = str(tmp_path / "orbax_ckpt")
    eng.save(SaveLoadMeta(path=path, weight_format="orbax", with_optim=True))
    assert os.path.isdir(os.path.join(path, "orbax_state"))

    eng2 = _make_engine(cpu_devices)
    eng2.load(SaveLoadMeta(path=path, weight_format="orbax", with_optim=True))
    assert eng2.get_version() == 5
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(eng.params),
        jax.tree_util.tree_leaves_with_path(eng2.params),
    ):
        assert pa == pb
        assert a.sharding == b.sharding, f"sharding lost for {pa}"
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng.destroy()
    eng2.destroy()


# -- crash-atomic versioned recovery (ISSUE 14 tentpole) ---------------------


class _FakeStateEngine:
    """Tiny engine standing in for JaxLMEngine: checkpoint = one json file,
    so the atomic-layout / torn-skip / prune mechanics are testable without
    building a real model."""

    def __init__(self, weight=0.0):
        self.weight = float(weight)
        self._version = 0
        self.pushed = 0

    def save(self, meta):
        os.makedirs(meta.path, exist_ok=True)
        import json

        with open(os.path.join(meta.path, "state.json"), "w") as f:
            json.dump(dict(weight=self.weight, version=self._version), f)

    def load(self, meta):
        import json

        with open(os.path.join(meta.path, "state.json")) as f:
            st = json.load(f)
        self.weight = st["weight"]

    def get_version(self):
        return self._version

    def set_version(self, v):
        self._version = v

    def update_weights(self, meta):
        self.pushed += 1


def _rcfg(tmp_path, **kw):
    kw.setdefault("freq_steps", 1)
    return RecoverConfig(
        experiment_name="atom", trial_name="t", fileroot=str(tmp_path),
        mode="auto", **kw
    )


def _si(g):
    return StepInfo(epoch=0, epoch_step=g, global_step=g, steps_per_epoch=100)


def test_dump_layout_is_committed_and_verified(tmp_path):
    cfg = _rcfg(tmp_path)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine(weight=1.5)
    path = h.dump(eng, _si(0), force=True)
    assert path is not None and path.endswith("step-0")
    assert os.path.isfile(os.path.join(path, "MANIFEST.json"))
    ok, reason = verify_step_dir(path)
    assert ok, reason
    root = recover_root(cfg)
    assert not any(n.endswith(".tmp") for n in os.listdir(root))
    assert check_if_auto_recover(cfg)


def test_keep_last_prunes_oldest(tmp_path):
    cfg = _rcfg(tmp_path, keep_last=2)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine()
    for g in range(4):
        assert h.dump(eng, _si(g), force=True) is not None
    root = recover_root(cfg)
    steps = sorted(n for n in os.listdir(root) if n.startswith("step-"))
    assert steps == ["step-2", "step-3"]


def test_load_skips_torn_newest_falls_back(tmp_path):
    """A torn newest checkpoint (crash mid-dump or bit rot) costs one
    recovery point, never the run: load lands on the predecessor."""
    reset_metrics()
    cfg = _rcfg(tmp_path, keep_last=2)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine(weight=10.0)
    h.dump(eng, _si(0), force=True)
    eng.weight = 20.0
    eng.set_version(1)
    newest = h.dump(eng, _si(1), force=True)
    # tear the newest: truncate the engine state behind the manifest
    with open(os.path.join(newest, "checkpoint", "state.json"), "w") as f:
        f.write("{")
    ok, _ = verify_step_dir(newest)
    assert not ok
    assert check_if_auto_recover(cfg)  # step-0 still verifies

    eng2 = _FakeStateEngine()
    h2 = RecoverHandler(cfg, FT)
    info = h2.load(eng2)
    assert info is not None
    assert info.last_step_info.global_step == 0
    assert eng2.weight == 10.0
    assert eng2.get_version() == 0
    assert get_metrics().get("recover_torn_skipped_total", 0) == 1


def test_check_if_auto_recover_reports_half_deleted_dir(tmp_path):
    """ISSUE 14 satellite: a half-deleted checkpoint dir must read as "no
    recoverable state" up front instead of exploding at load time."""
    cfg = _rcfg(tmp_path)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine()
    path = h.dump(eng, _si(0), force=True)
    os.remove(os.path.join(path, "recover_info.pkl"))
    assert not check_if_auto_recover(cfg)
    assert RecoverHandler(cfg, FT).load(_FakeStateEngine()) is None


@pytest.mark.parametrize("site", [
    "recover.dump.save",    # mid engine.save: a torn tmp dir left behind
    "recover.dump.info",    # saved, the recover info not yet written
    "recover.dump.marker",  # the save-vs-marker gap: sealed but uncommitted
])
def test_dump_failure_degrades_not_raises(tmp_path, site):
    """A failed dump (an injected abort at each of its seams: a trainer
    killed there leaves the same files) logs + counts + leaves the
    previous committed step intact; the loop keeps training, and a restart
    resumes from that step with its engine version."""
    from areal_tpu.core.fault_injection import (
        FaultPlan, FaultPoint, configure, deactivate,
    )

    reset_metrics()
    cfg = _rcfg(tmp_path, keep_last=2)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine(weight=7.0)
    h.dump(eng, _si(0), force=True)
    configure(FaultPlan(seed=1, points=[
        FaultPoint(site=site, mode="abort", times=1)
    ]))
    try:
        assert h.dump(eng, _si(1), force=True) is None
    finally:
        deactivate()
    assert get_metrics().get("recover_dump_failures_total", 0) == 1
    # the crashed attempt is a .tmp dir, never a candidate; step-0 loads
    eng2 = _FakeStateEngine()
    info = RecoverHandler(cfg, FT).load(eng2)
    assert info is not None and info.last_step_info.global_step == 0
    assert info.last_step_info.next().global_step == 1  # resume lands on the killed step
    assert eng2.weight == 7.0 and eng2.get_version() == eng.get_version()
    # and the next gate retries successfully, replacing the torn tmp
    assert h.dump(eng, _si(1), force=True) is not None


def test_recover_handler_freq_ctl_roundtrip(tmp_path):
    """The handler's own gate state rides in the checkpoint: after resume
    it must not re-fire early or skip a dump (ISSUE 14 satellite)."""
    cfg = _rcfg(tmp_path, freq_steps=3)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine()
    fired = [h.dump(eng, _si(g)) is not None for g in range(4)]
    assert fired == [False, False, True, False]  # gate fires on the 3rd step

    h2 = RecoverHandler(cfg, FT)
    info = h2.load(_FakeStateEngine())
    assert info is not None
    # the committed state is the gate AS OF the fired dump (the g=3 check
    # happened after the commit and is rolled back with the crash). The
    # resumed gate continues that cadence exactly: three steps to the next
    # fire — not zero (immediate re-fire) and not a skipped save.
    fired2 = [h2.dump(eng, _si(g)) is not None for g in range(4, 8)]
    assert fired2 == [False, False, True, False]


def test_replayed_step_redump_displaces_atomically(tmp_path):
    """Re-dumping the same global step (a replayed step after recovery)
    must commit the new content and leave no .old/.tmp residue."""
    cfg = _rcfg(tmp_path)
    h = RecoverHandler(cfg, FT)
    eng = _FakeStateEngine(weight=1.0)
    p = h.dump(eng, _si(0), force=True)
    eng.weight = 2.0
    p2 = h.dump(eng, _si(0), force=True)
    assert p == p2
    ok, reason = verify_step_dir(p2)
    assert ok, reason
    eng2 = _FakeStateEngine()
    RecoverHandler(cfg, FT).load(eng2)
    assert eng2.weight == 2.0
    root = recover_root(cfg)
    assert all(not n.endswith((".tmp", ".old")) for n in os.listdir(root))
