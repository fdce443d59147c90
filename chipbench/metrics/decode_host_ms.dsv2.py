"""The host's part of the DeepSeek-V2 cell's decode steps: the quantity
``decode_host_ms.lm`` reads, from the same step records paired with the
program's ``lm.step`` spans, which the ``lm_moe`` driver keeps as ``lm``
does."""
from pathlib import Path

from chipbench.harness import load_reader

read = load_reader(Path(__file__).resolve().parents[2], "decode_host_ms.lm")
