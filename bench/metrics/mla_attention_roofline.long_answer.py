"""mla_attention_roofline.long_answer: the mla_attention kernel's roofline time over its device time (kernel layer), long-answer cells; sizes from the configuration file of the cell's model."""
import json
import pathlib

from benchkit import mla

MODEL = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                    / "deepseek_v2_lite.json").read_text())["model"]


def read(run):
    return mla.kernel_roofline(run, MODEL)
