"""Golden-loss SFT regression gate.

Parity: the reference's SFT integration test asserts per-step losses match
a stored `ref_losses.json` (areal/tests/sft/, SURVEY.md §4) — the guard
against silent numerical regressions in the train path. Golden values were
produced by this exact scenario (fixed seeds, dp4·tp2 mesh on the 8-CPU
devices) at the commit that introduced this test; a legitimate numerical
change (e.g. a different reduction order) must regenerate them
consciously, not silently.

The values depend on the installed JAX: the parameter init draws from its
random stream (`jax_threefry_partitionable` changed that stream between
releases) and XLA:CPU picks the reduction orders. The golden file names the
versions that wrote it; after an upgrade, re-record it on purpose with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=. python tests/test_sft_golden.py
"""

import json
import os

import numpy as np

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import (
    MicroBatchSpec,
    OptimizerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.sft.lm_engine import JaxLMEngine
from areal_tpu.models.qwen2 import ModelConfig
from areal_tpu.utils.data import pad_sequences_to_tensors

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_sft_losses.json")


def _sft_losses() -> list[float]:
    cfg = TrainEngineConfig(
        experiment_name="golden",
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
    eng.model_config = ModelConfig(
        vocab_size=64,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        dtype="float32",
        param_dtype="float32",
    )
    eng.create_process_group(
        ParallelStrategy(data_parallel_size=4, tensor_parallel_size=2)
    )
    eng.initialize(None, FinetuneSpec(1, 64, 8))
    rng = np.random.RandomState(7)
    losses = []
    for _ in range(6):
        seqs = []
        for L in (9, 13, 7, 11):
            ids = rng.randint(1, 64, (L,))
            mask = np.zeros(L, dtype=np.int32)
            mask[L // 2 :] = 1
            seqs.append(dict(input_ids=ids, loss_mask=mask))
        losses.append(
            float(eng.train_lm(pad_sequences_to_tensors(seqs))["loss"])
        )
    eng.destroy()
    return losses


def test_sft_losses_match_golden(cpu_devices):
    with open(GOLDEN) as f:
        golden = json.load(f)
    np.testing.assert_allclose(
        _sft_losses(), golden["losses"], rtol=1e-4, atol=1e-5,
        err_msg=f"golden written by {golden['written_by']}",
    )


if __name__ == "__main__":
    import jax
    import jaxlib

    with open(GOLDEN, "w") as f:
        json.dump(
            {
                "written_by": {
                    "jax": jax.__version__,
                    "jaxlib": jaxlib.__version__,
                    "numpy": np.__version__,
                },
                "losses": _sft_losses(),
            },
            f,
            indent=1,
        )
        f.write("\n")
