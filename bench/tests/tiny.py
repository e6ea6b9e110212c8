"""A cell shrunk for the CPU: the harness, the program and the reference
run end to end at these sizes in under a minute. The configuration's
widths are kept; the pool, the fleet, the images and the batch shrink."""
import jax

import harness

CFG = {"images": 96, "n_vehicles": 6, "image_shape": [16, 16, 3],
       "batch_size": 8}
TRAFFIC = {"vehicles_per_round": 2}


def shrink(monkeypatch, tmp_path, traffic=None, cfg=None):
    """Point the harness at CPU devices and the tiny sizes (`cfg` and
    `traffic` replace more keys)."""
    full = harness.cell

    def cell(bench, name):
        c = full(bench, name)
        c["cfg"] = {**c["cfg"], **CFG, **(cfg or {})}
        c["traffic"] = {**c["traffic"], **TRAFFIC, **(traffic or {})}
        return c

    monkeypatch.setattr(harness, "cell", cell)
    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
