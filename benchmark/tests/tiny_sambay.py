"""The SambaY cell cut to a size a CPU test can hold: the committed cell's
files with the `sambay-tiny` preset's model laid over them. Never a
measurement."""
import dataclasses

from benchmark import harness


def tiny_sambay_cell(name: str = "phi4flash.train", *, compute_dtype: str = "float32") -> dict:
    from glom_tpu.utils.presets import get_preset

    cell = harness.load_cell(name)
    preset = get_preset("sambay-tiny")
    cf = cell["config_file"]
    cf["preset"] = "sambay-tiny"
    cf["model"] = dataclasses.asdict(preset.model)
    cf["train"] = {"batch_per_chip": 2, "learning_rate": 3e-4,
                   "compute_dtype": compute_dtype, "remat": True}
    cell["traffic_file"].update(seq_len=preset.model.seq_len)
    return cell
