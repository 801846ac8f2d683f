"""The Laguna cell cut to a size a CPU test can hold: the committed cell's
files with the `laguna-tiny` preset's model laid over them. Never a
measurement."""
import dataclasses

from benchmark import harness


def tiny_laguna_cell(name: str = "lagunaxs2.train", *, compute_dtype: str = "float32") -> dict:
    from glom_tpu.utils.presets import get_preset

    cell = harness.load_cell(name)
    preset = get_preset("laguna-tiny")
    cf = cell["config_file"]
    cf["preset"] = "laguna-tiny"
    cf["model"] = dataclasses.asdict(preset.model)
    cf["train"] = {"batch_per_chip": 2, "learning_rate": 3e-4,
                   "compute_dtype": compute_dtype, "remat": True}
    cell["traffic_file"].update(seq_len=preset.model.seq_len)
    return cell
