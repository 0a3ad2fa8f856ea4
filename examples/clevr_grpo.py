"""Vision GRPO on CLEVR counting — RLVR for a vision-language model: the
processor renders multimodal chat prompts, image patches ride the request
to the decode engine's vision tower, and training stays token-only.

Parity: /root/reference/examples/vlm/clevr_count_70k_grpo.py (Qwen2.5-VL
on clevr_count_70k with a boxed-count binary reward). TPU differences: the
in-process decode engine owns the vision tower (models/qwen2_vl.py,
m-rope + window-major patch encoding) instead of an SGLang server.

Usage:

  # fully-offline smoke (CPU): tiny tower + synthetic counting images
  python examples/clevr_grpo.py --config examples/configs/clevr_grpo.yaml \\
      tokenizer_path=synthetic-arith train_dataset.path=synthetic-vision \\
      actor.path= decode.model_path= actor.init_from_scratch=true

  # single-host TPU, Qwen2.5-VL-3B on clevr_count_70k (hub access):
  python examples/clevr_grpo.py --config examples/configs/clevr_grpo.yaml
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from gsm8k_grpo import main as grpo_main


def main(argv):
    grpo_main(list(argv) + ["workflow=vision_rlvr"])


if __name__ == "__main__":
    from areal_tpu.utils.experiment import run_with_status

    run_with_status(main, sys.argv[1:])
