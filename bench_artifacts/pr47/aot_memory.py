"""What XLA:TPU plans for `jit_grad_step` with each of `hbm.REMAT_SETS` kept,
compiled here for a described v5e (no chip): `memory_analysis()` beside the
closed form of `utils/hbm.py`, and the count of `%flash_fwd` in the program.

    JAX_PLATFORMS=cpu python bench_artifacts/pr47/aot_memory.py 0.5b 8192 1
    JAX_PLATFORMS=cpu python bench_artifacts/pr47/aot_memory.py 1.5b 16384 4

A model may also be one of the benchmark's configuration files (a sparse or a
linear one: the full-recompute step is priced by the dense closed form for
them too), with the sets to compile as a fourth argument:

    JAX_PLATFORMS=cpu python bench_artifacts/pr47/aot_memory.py \
        benchmark/configs/qwen3-next-80b-a3b.json 16384 4 0,1,2
"""

import json
import sys

import jax
from jax.experimental import topologies

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import MicroBatchSpec, OptimizerConfig, TrainEngineConfig
from areal_tpu.engine.sft.lm_engine import JaxLMEngine
from areal_tpu.models.qwen2 import ModelConfig
from areal_tpu.ops import flash_attention
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.utils import hbm

MODELS = {
    "0.5b": dict(hidden_size=896, intermediate_size=4864, num_hidden_layers=24,
                 num_attention_heads=14, num_key_value_heads=2),
    "1.5b": dict(hidden_size=1536, intermediate_size=8960, num_hidden_layers=28,
                 num_attention_heads=12, num_key_value_heads=2),
}


def main(model: str, tokens: int, chips: int, sets=None):
    flash_attention._default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    attn_impl = "ring" if chips > 1 else "flash"
    if model in MODELS:
        cfg = ModelConfig(
            vocab_size=151936, tie_word_embeddings=True, dtype="bfloat16",
            param_dtype="bfloat16", remat=True, scan_layers=True,
            attn_impl=attn_impl, **MODELS[model])
    else:
        with open(model) as f:
            cfg = ModelConfig.from_hf_config(
                json.load(f), dtype="bfloat16", param_dtype="bfloat16", remat=True,
                attn_impl=attn_impl)
    for n in (range(len(hbm.REMAT_SETS)) if sets is None else sets):
        eng = JaxLMEngine(TrainEngineConfig(
            experiment_name="aot", trial_name="aot", path="", init_from_scratch=True,
            dtype="bfloat16", mb_spec=MicroBatchSpec(max_tokens_per_mb=tokens),
            optimizer=OptimizerConfig(lr=1e-6, lr_scheduler_type="constant",
                                      warmup_steps_proportion=0.0, gradient_clipping=1.0),
            gradient_checkpointing=True))
        eng.model_config = cfg
        strategy = ParallelStrategy(data_parallel_size=chips)
        eng.parallel_strategy = strategy
        eng.mesh = mesh_lib.build_mesh(strategy, devices=list(topo.devices[:chips]))
        mesh_lib.set_current_mesh(eng.mesh)
        per_chip = tokens // chips
        kept = 0
        if hasattr(hbm, "remat_kept_bytes"):  # (the parent tree has neither)
            kept = hbm.remat_kept_bytes(cfg, per_chip, n, ring_steps=chips)
            eng._remat_choice[tokens, True] = (n, kept)
        eng_own = hbm.param_count(cfg) * (2 + 4 + 4 + 2) // chips
        try:
            report = eng.plan_compile_check(mb_tokens=tokens)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"sets": n, "error": repr(e)[:2000]}))
            continue
        ma = report["grad_step"]
        est = hbm.estimate_train_hbm(cfg, dp=chips, microbatch_tokens=tokens)
        print(json.dumps({
            "model": model, "tokens": tokens, "chips": chips, "sets": n,
            "kept_closed_form": kept, "resident_closed_form": eng_own,
            "step_closed_form": est.activation_bytes + est.logits_bytes
            + getattr(est, "grad_transient_bytes", 0),
            "grad_step": ma, "apply_update": report.get("apply_update")}))
        sys.stdout.flush()


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
         [int(x) for x in sys.argv[4].split(",")] if len(sys.argv) > 4 else None)
