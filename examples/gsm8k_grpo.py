"""Async GRPO on GSM8K — the runnable entry point of the TPU build.

Parity: /root/reference/examples/math/gsm8k_grpo.py:34 (single-file training
script; user owns the loop). TPU differences: the train engine is the GSPMD
JaxPPOActor (one process drives all local chips), and rollout either runs
in-process on the same chips (COLOCATE — the default when `allocation_mode`
is empty or has no `+`) or against decode-server subprocesses spawned by the
local launcher (DECOUPLED — `allocation_mode: "jax:d1t1+d1"` style).

Usage:

  # fully offline smoke on the CPU (tiny model, synthetic arithmetic dataset):
  JAX_PLATFORMS=cpu python examples/gsm8k_grpo.py \
      --config examples/configs/arith_grpo_smoke.yaml

  # the same loop at Qwen2.5-0.5B width on one TPU chip, weights from a seed
  # (what chip_smoke.py runs):
  python examples/gsm8k_grpo.py \
      --config examples/configs/qwen2.5_0.5b_grpo_smoke.yaml

  # single-host TPU, colocated decode + train, Qwen2.5-0.5B on GSM8K:
  python examples/gsm8k_grpo.py --config examples/configs/gsm8k_grpo.yaml

  # decoupled: launcher spawns decode server(s) then this trainer:
  python -m areal_tpu.launcher.local examples/gsm8k_grpo.py \
      --config examples/configs/gsm8k_grpo.yaml \
      allocation_mode=jax:d1t1+d1

Override any config field with key=value, e.g. `actor.optimizer.lr=1e-5`.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.alloc_mode import AllocationMode, AllocationType
from areal_tpu.api.cli_args import GRPOConfig, load_expr_config, save_config
from areal_tpu.api.io_struct import FinetuneSpec, StepInfo, WeightUpdateMeta
from areal_tpu.dataset import (
    SimpleDataLoader,
    get_custom_dataset,
    load_tokenizer,
)
from areal_tpu.engine.ppo.actor import JaxPPOActor
from areal_tpu.utils import seeding, stats_tracker
from areal_tpu.utils.evaluator import Evaluator
from areal_tpu.utils.recover import RecoverHandler, ledger_wal_path
from areal_tpu.utils.saver import Saver
from areal_tpu.utils.stats_logger import StatsLogger
from areal_tpu.workflow.rlvr import RLVRWorkflow


def gsm8k_reward_fn(prompt, completion, prompt_ids, completion_ids, **data):
    from areal_tpu.reward.math_parser import math_verify_reward

    return math_verify_reward(prompt, completion, prompt_ids, completion_ids, **data)




def pick_reward_fn(dataset_path: str):
    name = dataset_path.split("/")[-1].lower()
    if name == "countdown":
        from areal_tpu.reward.countdown import countdown_reward

        return countdown_reward
    if name == "clevr_count_70k":
        from areal_tpu.reward.vqa import clevr_count_reward

        return clevr_count_reward
    if name == "geometry3k":
        from areal_tpu.reward.vqa import geometry3k_reward

        return geometry3k_reward
    if name == "synthetic-arith":
        from areal_tpu.dataset.arith import arith_reward_fn

        return arith_reward_fn
    if name == "synthetic-vision":
        from areal_tpu.reward.vqa import synthetic_vision_reward

        return synthetic_vision_reward
    return gsm8k_reward_fn


def build_rollout(config: GRPOConfig, alloc: AllocationMode, actor, tokenizer):
    """COLOCATE -> in-process decode engine sharing the actor's chips;
    DECOUPLED -> HTTP client over launcher-spawned decode servers."""
    if alloc.type_ == AllocationType.DECOUPLED_TRAIN:
        from areal_tpu.core.remote_inf_engine import (
            JaxDecodeBackend,
            RemoteInfEngine,
        )

        rollout = RemoteInfEngine(
            config.rollout, JaxDecodeBackend(), tokenizer=tokenizer
        )
        rollout.initialize(
            train_data_parallel_size=actor.data_parallel_world_size
        )
        meta = WeightUpdateMeta(type="dcn")
        return rollout, meta
    # COLOCATE: decode engine on the trainer's devices, memory weight updates
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    # tokenizer enables server-side stop STRINGS (TIR's ``` terminator);
    # stop token ids work either way
    rollout = JaxDecodeEngine(config.decode, config.rollout, tokenizer=tokenizer)
    rollout.set_model(actor.params, actor.model_config)
    if config.workflow == "vision_rlvr" and not config.decode.model_path:
        # offline vision smoke: tiny tower + smoke image token, so the
        # synthetic-vision dataset serves end-to-end without hub access
        import jax

        from areal_tpu.models.qwen2_vl import init_vision_params
        from areal_tpu.models.smoke import (
            SMOKE_IMAGE_TOKEN,
            smoke_mrope_sections,
            smoke_vision_config,
        )

        vis = smoke_vision_config()
        rollout.set_vision_model(
            init_vision_params(vis, jax.random.PRNGKey(7)),
            vis,
            SMOKE_IMAGE_TOKEN,
            mrope_sections=smoke_mrope_sections(),
        )
    rollout.initialize()
    return rollout, WeightUpdateMeta.from_memory(alloc)


def main(args, after_step=None):
    """Run the loop; returns each step's list of per-minibatch stats.

    `after_step(global_step, batch, actor, rollout)`, if given, is called at
    the end of every step, rollouts still paused and both engines live
    (chip_smoke.py reads the batch's weight versions there and, on the last
    step, the engines' shardings and loaded programs)."""
    config, _ = load_expr_config(args, GRPOConfig)
    config: GRPOConfig

    rank = int(os.getenv("AREAL_TPU_PROCESS_ID", "0"))
    seeding.set_random_seed(config.seed, key=f"trainer{rank}")
    tokenizer = load_tokenizer(config.tokenizer_path)

    from areal_tpu.utils import name_resolve

    name_resolve.reconfigure(config.cluster.name_resolve)
    alloc = AllocationMode.from_str(config.allocation_mode)

    actor = JaxPPOActor(config.actor)
    if not config.actor.path:
        # Offline smoke mode: no HF checkpoint — train the canonical tiny
        # from-scratch decoder (shared with the decode server's
        # --scratch-model mode so decoupled smoke runs line up).
        from areal_tpu.models.smoke import smoke_model_config

        actor.model_config = smoke_model_config(
            dtype=config.actor.dtype,
            vocab_size=getattr(tokenizer, "vocab_size", None),
        )
    actor.create_process_group(alloc.train)

    train_dataset = get_custom_dataset(
        path=config.train_dataset.path,
        split="train",
        type=config.train_dataset.type or "rl",
        tokenizer=tokenizer,
        max_length=config.train_dataset.max_length,
        rank=actor.data_parallel_rank,
        world_size=actor.data_parallel_world_size,
    )
    valid_dataset = get_custom_dataset(
        path=(config.valid_dataset or config.train_dataset).path,
        split="test",
        type=(config.valid_dataset or config.train_dataset).type or "rl",
        tokenizer=tokenizer,
        max_length=(config.valid_dataset or config.train_dataset).max_length,
        rank=actor.data_parallel_rank,
        world_size=actor.data_parallel_world_size,
    )
    train_dataloader = SimpleDataLoader(
        train_dataset,
        batch_size=config.train_dataset.batch_size,
        shuffle=config.train_dataset.shuffle,
        seed=config.seed,
        drop_last=config.train_dataset.drop_last,
    )
    valid_dataloader = SimpleDataLoader(
        valid_dataset,
        batch_size=(config.valid_dataset or config.train_dataset).batch_size,
        shuffle=False,
    )
    steps_per_epoch = len(train_dataloader)
    ft_spec = FinetuneSpec(
        total_train_epochs=config.total_train_epochs,
        dataset_size=steps_per_epoch * config.train_dataset.batch_size,
        train_batch_size=config.train_dataset.batch_size,
    )
    actor.initialize(None, ft_spec)

    rollout, weight_update_meta = build_rollout(config, alloc, actor, tokenizer)
    actor.connect_engine(rollout, weight_update_meta)

    ref = None
    if config.actor.kl_ctl > 0 and config.ref is not None and config.ref.path:
        ref = JaxPPOActor(config.ref)
        ref.model_config = actor.model_config
        ref.create_process_group(alloc.train)
        ref.initialize(None, ft_spec)

    reward_fn = pick_reward_fn(config.train_dataset.path)
    if getattr(tokenizer, "eos_token_id", None) is not None:
        if tokenizer.eos_token_id not in config.gconfig.stop_token_ids:
            config.gconfig.stop_token_ids.append(tokenizer.eos_token_id)
    if config.workflow not in ("rlvr", "multi_turn", "vision_rlvr", "tir"):
        raise ValueError(
            f"workflow={config.workflow!r} not in "
            "('rlvr', 'multi_turn', 'vision_rlvr', 'tir')"
        )
    processor = None
    if config.workflow == "vision_rlvr":
        from areal_tpu.models.smoke import OFFLINE_SENTINELS

        if config.tokenizer_path not in OFFLINE_SENTINELS:
            from transformers import AutoProcessor

            processor = AutoProcessor.from_pretrained(config.tokenizer_path)
        # offline: the synthetic-vision dataset ships pre-tokenized prompts
        # + pre-processed patches, so no processor is needed

    def make_workflow(gconfig, dump_dir=None):
        if config.workflow == "multi_turn":
            # self-correction loop: wrong answer -> feedback prompt ->
            # retry, rewards discounted per extra turn (ref:
            # examples/multi-turn-math/train.py)
            from areal_tpu.workflow.multi_turn import MultiTurnWorkflow

            return MultiTurnWorkflow(
                reward_fn=reward_fn,
                gconfig=gconfig,
                tokenizer=tokenizer,
                max_turns=config.max_turns,
                turn_discount=config.turn_discount,
                dump_dir=dump_dir,
            )
        if config.workflow == "tir":
            # tool-integrated reasoning: ```python blocks execute in a
            # sandbox mid-generation (ref: examples/tir/tir_workflow.py)
            from areal_tpu.workflow.tir import TIRWorkflow

            return TIRWorkflow(
                reward_fn=reward_fn,
                gconfig=gconfig,
                tokenizer=tokenizer,
                max_tool_calls=config.max_tool_calls,
                tool_timeout_seconds=config.tool_timeout_seconds,
                dump_dir=dump_dir,
            )
        if config.workflow == "vision_rlvr":
            from areal_tpu.workflow.vision_rlvr import VisionRLVRWorkflow

            return VisionRLVRWorkflow(
                reward_fn=reward_fn,
                gconfig=gconfig,
                tokenizer=tokenizer,
                processor=processor,
                dump_dir=dump_dir,
            )
        return RLVRWorkflow(
            reward_fn=reward_fn,
            gconfig=gconfig,
            tokenizer=tokenizer,
            dump_dir=dump_dir,
        )

    workflow = make_workflow(
        config.gconfig,
        dump_dir=os.path.join(
            StatsLogger.get_log_path(config.stats_logger), "generated"
        ),
    )
    eval_workflow = make_workflow(config.gconfig.new(temperature=0.6))

    saver = Saver(config.saver, ft_spec)
    stats_logger = StatsLogger(config.stats_logger, ft_spec)
    evaluator = Evaluator(config.evaluator, ft_spec)
    recover_handler = RecoverHandler(config.recover, ft_spec)
    # exactly-once sample accounting: journal consumed batches to a WAL
    # colocated with the recovery state; load() rolls it back to the
    # committed seq and restores the staleness cap from consumed counts
    if hasattr(rollout, "attach_ledger_wal"):
        rollout.attach_ledger_wal(ledger_wal_path(config.recover))
    recover_info = recover_handler.load(
        actor,
        saver,
        evaluator,
        train_dataloader,
        inference_engine=rollout,
        weight_update_meta=weight_update_meta,
    )
    start_step = (
        recover_info.last_step_info.next().global_step
        if recover_info is not None
        else 0
    )
    if rank == 0:
        save_config(config, StatsLogger.get_log_path(config.stats_logger))

    max_steps = config.total_train_steps or (
        config.total_train_epochs * steps_per_epoch
    )

    history = []
    for global_step in range(start_step, max_steps):
        epoch = global_step // steps_per_epoch
        step = global_step % steps_per_epoch
        step_info = StepInfo(
            global_step=global_step,
            epoch=epoch,
            epoch_step=step,
            steps_per_epoch=steps_per_epoch,
        )

        with stats_tracker.record_timing("rollout"):
            if config.async_training:
                batch = rollout.prepare_batch(
                    train_dataloader, workflow=workflow
                )
            else:
                batch = rollout.rollout_batch(
                    next(iter(train_dataloader)), workflow=workflow
                )
        if config.actor.recompute_logprob or config.actor.use_decoupled_loss:
            with stats_tracker.record_timing("recompute_logp"):
                batch["prox_logp"] = actor.compute_logp(batch)

        if ref is not None:
            with stats_tracker.record_timing("ref_logp"):
                batch["ref_logp"] = ref.compute_logp(batch)

        with stats_tracker.record_timing("compute_advantage"):
            actor.compute_advantages(batch)

        with (
            stats_tracker.record_timing("train_step"),
            stats_tracker.scope("grpo_actor"),
        ):
            stats = actor.ppo_update(batch)

        rollout.pause()
        with stats_tracker.record_timing("update_weights"):
            actor.set_version(global_step + 1)
            actor.update_weights(weight_update_meta)
            rollout.set_version(global_step + 1)

        with stats_tracker.record_timing("save"):
            saver.save(actor, epoch, step, global_step, tokenizer=tokenizer)

        with stats_tracker.record_timing("checkpoint_for_recover"):
            recover_handler.dump(
                actor,
                step_info,
                saver,
                evaluator,
                train_dataloader,
                tokenizer=tokenizer,
                rollout=rollout,
            )

        with stats_tracker.record_timing("eval"):

            def evaluate_fn():
                cnt = 0
                for items in valid_dataloader:
                    for item in items:
                        rollout.submit(item, eval_workflow)
                        cnt += 1
                rollout.wait(cnt, timeout=None)

            evaluator.evaluate(evaluate_fn, epoch, step, global_step)

        stats[0].update(stats_tracker.export_all())
        stats_logger.commit(epoch, step, global_step, stats)
        history.append(stats)
        if after_step is not None:
            after_step(global_step, batch, actor, rollout)
        rollout.resume()

    stats_logger.close()
    rollout.destroy()
    if ref is not None:
        ref.destroy()
    actor.destroy()
    return history


if __name__ == "__main__":
    from areal_tpu.utils.experiment import run_with_status

    run_with_status(main, sys.argv[1:])
