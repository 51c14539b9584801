"""decode_mfu.long_answer: decode model FLOPs (absorbed MLA, the held share of the experts) in the traced window over its length and the bf16 peak (model step layer), long-answer cells; sizes from the configuration file of the cell's model."""
import json
import pathlib

from benchkit import mla

MODEL = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                    / "deepseek_v2_lite.json").read_text())["model"]


def read(run):
    return mla.decode_mfu(run, MODEL)
