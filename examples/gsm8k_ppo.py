"""PPO (actor + critic) on GSM8K — the value-function variant.

Parity: the reference's PPO recipes (PPOConfig in areal/api/cli_args.py:
1246; actor+critic pairs in areal/engine/ppo/). Identical loop shape to
examples/gsm8k_grpo.py plus: a critic engine computes per-token values
before the advantage pass (GAE uses them instead of group baselines) and
takes its own update per step.

Usage (same config system; `critic.*` keys configure the value model):

  python examples/gsm8k_ppo.py --config examples/configs/arith_grpo_smoke.yaml \
      actor.adv_norm.mean_level=batch actor.adv_norm.std_level=batch \
      actor.gae_lambda=0.95 actor.discount=1.0
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.alloc_mode import AllocationMode
from areal_tpu.api.cli_args import PPOConfig, load_expr_config, save_config
from areal_tpu.api.io_struct import FinetuneSpec, StepInfo
from areal_tpu.dataset import SimpleDataLoader, get_custom_dataset
from areal_tpu.engine.ppo.actor import JaxPPOActor
from areal_tpu.engine.ppo.critic import JaxPPOCritic
from areal_tpu.utils import name_resolve, seeding, stats_tracker
from areal_tpu.utils.recover import RecoverHandler
from areal_tpu.utils.saver import Saver
from areal_tpu.utils.stats_logger import StatsLogger
from areal_tpu.workflow.rlvr import RLVRWorkflow

from examples.gsm8k_grpo import build_rollout, load_tokenizer, pick_reward_fn


def main(args):
    config, _ = load_expr_config(args, PPOConfig)
    config: PPOConfig

    rank = int(os.getenv("AREAL_TPU_PROCESS_ID", "0"))
    seeding.set_random_seed(config.seed, key=f"trainer{rank}")
    tokenizer = load_tokenizer(config.tokenizer_path)
    name_resolve.reconfigure(config.cluster.name_resolve)
    alloc = AllocationMode.from_str(config.allocation_mode)

    actor = JaxPPOActor(config.actor)
    critic = JaxPPOCritic(config.critic)
    if not config.actor.path:
        from areal_tpu.models.smoke import smoke_model_config

        actor.model_config = smoke_model_config(
            dtype=config.actor.dtype,
            vocab_size=getattr(tokenizer, "vocab_size", None),
        )
    if not config.critic.path:
        from areal_tpu.models.smoke import smoke_model_config

        critic.model_config = smoke_model_config(
            dtype=config.critic.dtype,
            vocab_size=getattr(tokenizer, "vocab_size", None),
            is_critic=True,
        )
    actor.create_process_group(alloc.train)
    critic.create_process_group(alloc.train)

    train_dataset = get_custom_dataset(
        path=config.train_dataset.path,
        split="train",
        type=config.train_dataset.type or "rl",
        tokenizer=tokenizer,
        max_length=config.train_dataset.max_length,
        rank=actor.data_parallel_rank,
        world_size=actor.data_parallel_world_size,
    )
    train_dataloader = SimpleDataLoader(
        train_dataset,
        batch_size=config.train_dataset.batch_size,
        shuffle=config.train_dataset.shuffle,
        seed=config.seed,
    )
    steps_per_epoch = len(train_dataloader)
    ft_spec = FinetuneSpec(
        total_train_epochs=config.total_train_epochs,
        dataset_size=steps_per_epoch * config.train_dataset.batch_size,
        train_batch_size=config.train_dataset.batch_size,
    )
    actor.initialize(None, ft_spec)
    critic.initialize(None, ft_spec)

    rollout, weight_update_meta = build_rollout(config, alloc, actor, tokenizer)
    actor.connect_engine(rollout, weight_update_meta)

    reward_fn = pick_reward_fn(config.train_dataset.path)
    if getattr(tokenizer, "eos_token_id", None) is not None:
        if tokenizer.eos_token_id not in config.gconfig.stop_token_ids:
            config.gconfig.stop_token_ids.append(tokenizer.eos_token_id)
    workflow = RLVRWorkflow(
        reward_fn=reward_fn, gconfig=config.gconfig, tokenizer=tokenizer
    )

    saver = Saver(config.saver, ft_spec)
    critic_saver = Saver(config.saver, ft_spec)
    stats_logger = StatsLogger(config.stats_logger, ft_spec)
    # RecoverHandler checkpoints ONE engine per recover root; restoring the
    # actor while the critic re-initializes would silently corrupt GAE
    # baselines, so recover is rejected here until a two-engine handler
    # exists.
    if config.recover.mode != "disabled":
        raise NotImplementedError(
            "gsm8k_ppo.py does not support recover yet: the recover "
            "checkpoint covers the actor only and a restored run would pair "
            "it with a fresh critic; set recover.mode=disabled"
        )
    recover_handler = RecoverHandler(config.recover, ft_spec)
    start_step = 0
    if rank == 0:
        save_config(config, StatsLogger.get_log_path(config.stats_logger))
    max_steps = config.total_train_steps or (
        config.total_train_epochs * steps_per_epoch
    )

    for global_step in range(start_step, max_steps):
        epoch = global_step // steps_per_epoch
        step = global_step % steps_per_epoch

        with stats_tracker.record_timing("rollout"):
            batch = rollout.prepare_batch(train_dataloader, workflow=workflow)

        if config.actor.recompute_logprob or config.actor.use_decoupled_loss:
            with stats_tracker.record_timing("recompute_logp"):
                batch["prox_logp"] = actor.compute_logp(batch)

        with stats_tracker.record_timing("critic_values"):
            batch["values"] = critic.compute_values(batch)

        with stats_tracker.record_timing("compute_advantage"):
            actor.compute_advantages(batch)

        with (
            stats_tracker.record_timing("train_step"),
            stats_tracker.scope("ppo_actor"),
        ):
            stats = actor.ppo_update(batch)

        with (
            stats_tracker.record_timing("critic_step"),
            stats_tracker.scope("ppo_critic"),
        ):
            critic_stats = critic.ppo_update(batch)
            stats[0].update(critic_stats[0])

        rollout.pause()
        with stats_tracker.record_timing("update_weights"):
            actor.set_version(global_step + 1)
            actor.update_weights(weight_update_meta)
            rollout.set_version(global_step + 1)
            critic.set_version(global_step + 1)

        saver.save(actor, epoch, step, global_step, tokenizer=tokenizer)
        critic_saver.save(
            critic, epoch, step, global_step, name="critic",
            tokenizer=tokenizer,
        )
        recover_handler.dump(
            actor,
            StepInfo(
                global_step=global_step,
                epoch=epoch,
                epoch_step=step,
                steps_per_epoch=steps_per_epoch,
            ),
            saver,
            None,
            train_dataloader,
            tokenizer=tokenizer,
        )
        stats[0].update(stats_tracker.export_all())
        stats_logger.commit(epoch, step, global_step, stats)
        rollout.resume()

    stats_logger.close()
    rollout.destroy()
    critic.destroy()
    actor.destroy()


if __name__ == "__main__":
    from areal_tpu.utils.experiment import run_with_status

    run_with_status(main, sys.argv[1:])
