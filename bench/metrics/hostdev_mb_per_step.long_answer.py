"""hostdev_mb_per_step.long_answer: megabytes between host and device per decode step, from counts and shapes of the latent pool (KV backend layer), long-answer cells; sizes from the configuration file of the cell's model."""
import json
import pathlib

from benchkit import mla

MODEL = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                    / "deepseek_v2_lite.json").read_text())["model"]


def read(run):
    return mla.hostdev_mb_per_step(run, MODEL)
