"""Share of the DeepSeek-V2 cell's window spent in ``LMServer.step`` calls
that admitted requests: the quantity ``admit_share.lm`` reads, from the
same step records, which the ``lm_moe`` driver keeps as ``lm`` does."""
from pathlib import Path

from chipbench.harness import load_reader

read = load_reader(Path(__file__).resolve().parents[2], "admit_share.lm")
