"""The Ouro cell cut to a size a CPU test can hold: the committed cell's files
with the `ouro-tiny` preset's model laid over them. Never a measurement."""
import dataclasses

from benchmark import harness


def tiny_ouro_cell(name: str = "ouro26b.train", *, compute_dtype: str = "float32",
                   on_kernels: bool = False) -> dict:
    """`on_kernels` keeps the cell's own requirement that every layer
    application's attention ran in the kernels, which no CPU run meets."""
    from glom_tpu.utils.presets import get_preset

    cell = harness.load_cell(name)
    preset = get_preset("ouro-tiny")
    cf = cell["config_file"]
    cf["preset"] = "ouro-tiny"
    cf["model"] = dataclasses.asdict(preset.model)
    cf["train"] = {"batch_per_chip": 2, "learning_rate": 3e-4,
                   "compute_dtype": compute_dtype, "remat": True}
    cf["bench"]["attention_on_kernels"] = on_kernels
    cell["traffic_file"].update(seq_len=preset.model.seq_len)
    return cell
