"""Model step, a mixture's share on one chip under sparse and sliding latent
attention (`models/dots3_note.py`): of the assignments the routers made in
the window's decode steps, the share that went to experts this chip holds
(`expert_assignments`) and not to another chip's (`assignments_elsewhere`)
— `moe.held_assignment_share`'s reading, under a name of its own because
the accepted entry lists another cell. 32 of 256 experts held under routing
that is uniform reads 12.5."""

from benchmark import manifest


def read(collected: dict):
    if collected["config"].get("model_type") != "dots3_note":
        return None
    return manifest.load_module(
        "layer_metrics", "moe.held_assignment_share").read(collected)
