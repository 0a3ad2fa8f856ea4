"""Tool-integrated reasoning (TIR) math RL — the model writes ```python
blocks that execute in a sandbox mid-generation, and the interpreter
output is spliced back into the context (masked from the loss).

Parity: /root/reference/examples/tir/ (tir_workflow.py: segment-wise
generation with tool-call interception, tool outputs loss-masked;
train_tir.py entry). The TPU build's TIRWorkflow (workflow/tir.py) runs
the same episode loop against the in-process decode engine or decode
servers; the sandbox is the subprocess-isolated runner of reward/tir
tooling (grandchild reaping, wall-clock timeout).

Usage:

  # offline smoke (CPU, synthetic arithmetic — tool calls optional):
  python examples/tir_math.py --config examples/configs/tir_math.yaml \\
      tokenizer_path=synthetic-arith train_dataset.path=synthetic-arith \\
      actor.path= decode.model_path= actor.init_from_scratch=true

  # single-host TPU, ToRL data with Qwen2.5-Math:
  python examples/tir_math.py --config examples/configs/tir_math.yaml
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from gsm8k_grpo import main as grpo_main


def main(argv):
    # the entry pins the workflow; everything else is the shared async-GRPO
    # loop (gsm8k_grpo.main), configured by tir_math.yaml
    grpo_main(list(argv) + ["workflow=tir"])


if __name__ == "__main__":
    from areal_tpu.utils.experiment import run_with_status

    run_with_status(main, sys.argv[1:])
