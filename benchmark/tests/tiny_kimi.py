"""The Kimi Linear cell cut to a size a CPU test can hold: the committed
cell's files with the `kimi-linear-tiny` preset's model laid over them. Never
a measurement."""
import dataclasses

from benchmark import harness


def tiny_kimi_cell(name: str = "kimilinear.train", *, compute_dtype: str = "float32") -> dict:
    from glom_tpu.utils.presets import get_preset

    cell = harness.load_cell(name)
    preset = get_preset("kimi-linear-tiny")
    cf = cell["config_file"]
    cf["preset"] = "kimi-linear-tiny"
    cf["model"] = dataclasses.asdict(preset.model)
    cf["train"] = {"batch_per_chip": 2, "learning_rate": 3e-4,
                   "compute_dtype": compute_dtype, "remat": True}
    cell["traffic_file"].update(seq_len=preset.model.seq_len)
    return cell
