"""Multi-turn self-correction math RL — wrong answers get feedback
("Your answer is incorrect. Please try again.") and another attempt, with
rewards discounted per extra turn.

Parity: /root/reference/examples/multi-turn-math/ (train.py +
multi_turn_workflow.py: evaluate each turn, append feedback on failure,
discount the final reward by gamma^turns). The TPU build's
MultiTurnWorkflow (workflow/multi_turn.py) keeps the whole conversation in
one token stream with feedback spans loss-masked, so the trainer consumes
an ordinary packed batch.

Usage:

  # offline smoke (CPU, synthetic arithmetic):
  python examples/multi_turn_math.py --config examples/configs/multi_turn_math.yaml \\
      tokenizer_path=synthetic-arith train_dataset.path=synthetic-arith \\
      actor.path= decode.model_path= actor.init_from_scratch=true

  # single-host TPU, GSM8K with Qwen2.5-0.5B:
  python examples/multi_turn_math.py --config examples/configs/multi_turn_math.yaml
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from gsm8k_grpo import main as grpo_main


def main(argv):
    grpo_main(list(argv) + ["workflow=multi_turn"])


if __name__ == "__main__":
    from areal_tpu.utils.experiment import run_with_status

    run_with_status(main, sys.argv[1:])
