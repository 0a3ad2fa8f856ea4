"""SFT on GSM8K (or the offline synthetic-arith set) — runnable entry point.

Parity: /root/reference/examples (SFT recipes); same config system and
launcher contract as examples/gsm8k_grpo.py, driving the packed-sequence
GSPMD LM engine instead of the RL stack.

Usage:

  # fully offline smoke (CPU or one chip):
  python examples/gsm8k_sft.py --config examples/configs/arith_sft_smoke.yaml

  # single-host TPU, Qwen2.5-0.5B on GSM8K:
  python examples/gsm8k_sft.py --config examples/configs/gsm8k_sft.yaml
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from areal_tpu.api.alloc_mode import AllocationMode
from areal_tpu.api.cli_args import SFTConfig, load_expr_config, save_config
from areal_tpu.api.io_struct import FinetuneSpec, StepInfo
from areal_tpu.dataset import (
    SimpleDataLoader,
    get_custom_dataset,
    load_tokenizer,
)
from areal_tpu.engine.sft.lm_engine import JaxLMEngine
from areal_tpu.utils import seeding, stats_tracker
from areal_tpu.utils.data import pad_sequences_to_tensors
from areal_tpu.utils.evaluator import Evaluator
from areal_tpu.utils.recover import RecoverHandler
from areal_tpu.utils.saver import Saver
from areal_tpu.utils.stats_logger import StatsLogger




def to_batch(items) -> dict:
    seqs = []
    for x in items:
        ids = np.asarray(x["input_ids"], dtype=np.int32)
        mask = np.asarray(
            x.get("loss_mask", np.ones_like(ids)), dtype=np.int32
        )
        seqs.append(dict(input_ids=ids, loss_mask=mask))
    return pad_sequences_to_tensors(seqs)


def main(args):
    config, _ = load_expr_config(args, SFTConfig)
    config: SFTConfig

    rank = int(os.getenv("AREAL_TPU_PROCESS_ID", "0"))
    seeding.set_random_seed(config.seed, key=f"trainer{rank}")
    tokenizer = load_tokenizer(config.tokenizer_path)

    from areal_tpu.utils import name_resolve

    name_resolve.reconfigure(config.cluster.name_resolve)
    alloc = AllocationMode.from_str(config.allocation_mode)

    engine = JaxLMEngine(config.model)
    if not config.model.path:
        from areal_tpu.models.smoke import smoke_model_config

        engine.model_config = smoke_model_config(
            dtype=config.model.dtype,
            vocab_size=getattr(tokenizer, "vocab_size", None),
        )
    engine.create_process_group(alloc.train)

    train_dataset = get_custom_dataset(
        path=config.train_dataset.path,
        split="train",
        type="sft",
        tokenizer=tokenizer,
        max_length=config.train_dataset.max_length,
        rank=engine.data_parallel_rank,
        world_size=engine.data_parallel_world_size,
    )
    valid_dataset = get_custom_dataset(
        path=(config.valid_dataset or config.train_dataset).path,
        split="test",
        type="sft",
        tokenizer=tokenizer,
        max_length=(config.valid_dataset or config.train_dataset).max_length,
        rank=engine.data_parallel_rank,
        world_size=engine.data_parallel_world_size,
    )
    train_dataloader = SimpleDataLoader(
        train_dataset,
        batch_size=config.train_dataset.batch_size,
        shuffle=config.train_dataset.shuffle,
        seed=config.seed,
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
    engine.initialize(None, ft_spec)

    saver = Saver(config.saver, ft_spec)
    stats_logger = StatsLogger(config.stats_logger, ft_spec)
    evaluator = Evaluator(config.evaluator, ft_spec)
    recover_handler = RecoverHandler(config.recover, ft_spec)
    recover_info = recover_handler.load(
        engine, saver, evaluator, train_dataloader
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

    global_step = start_step
    data_iter = iter(train_dataloader)
    while global_step < max_steps:
        try:
            items = next(data_iter)
        except StopIteration:
            data_iter = iter(train_dataloader)
            items = next(data_iter)
        epoch = global_step // steps_per_epoch
        step = global_step % steps_per_epoch

        with stats_tracker.record_timing("train_step"):
            stats = engine.train_lm(to_batch(items))
        engine.set_version(global_step + 1)

        saver.save(engine, epoch, step, global_step, tokenizer=tokenizer)
        recover_handler.dump(
            engine,
            StepInfo(
                global_step=global_step,
                epoch=epoch,
                epoch_step=step,
                steps_per_epoch=steps_per_epoch,
            ),
            saver,
            evaluator,
            train_dataloader,
            tokenizer=tokenizer,
        )

        def evaluate_fn():
            losses = [
                engine.evaluate_lm(to_batch(v_items))
                for v_items in valid_dataloader
            ]
            stats_tracker.scalar(eval_loss=float(np.mean(losses)))

        evaluator.evaluate(evaluate_fn, epoch, step, global_step)

        stats.update(stats_tracker.export_all())
        stats_logger.commit(epoch, step, global_step, stats)
        global_step += 1

    stats_logger.close()
    engine.destroy()


if __name__ == "__main__":
    from areal_tpu.utils.experiment import run_with_status

    run_with_status(main, sys.argv[1:])
