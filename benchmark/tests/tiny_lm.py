"""The language-model cell cut to a size a CPU test can hold: the committed
cell's files with the `hybrid-lm-tiny` preset's model laid over them. Never
a measurement."""
import dataclasses

from benchmark import harness


def tiny_lm_cell(name: str = "nemotron3super.train", *, compute_dtype: str = "float32") -> dict:
    from glom_tpu.utils.presets import get_preset

    cell = harness.load_cell(name)
    cf = cell["config_file"]
    cf["preset"] = "hybrid-lm-tiny"
    cf["model"] = dataclasses.asdict(get_preset("hybrid-lm-tiny").model)
    cf["train"] = {"batch_per_chip": 2, "learning_rate": 3e-4,
                   "compute_dtype": compute_dtype, "remat": True}
    cell["traffic_file"].update(seq_len=64)
    return cell
