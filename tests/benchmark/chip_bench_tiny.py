"""A cell of the chip benchmark shrunk to run on the CPU in seconds."""
import copy

from chipbench import spec


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    width, image = 1 / 16, 32
    scale = image / cfg["image_size"]

    def walk(nodes):
        for n in nodes:
            if n["op"] == "conv":
                if n["in_ch"] != cfg["channels"]:
                    n["in_ch"] = int(n["in_ch"] * width)
                n["out_ch"] = int(n["out_ch"] * width)
                n["in_hw"] = int(n["in_hw"] * scale)
            elif n["op"] == "branch":
                for path in n["paths"]:
                    walk(path)

    walk(cfg["layers"])
    cfg.update(width=width, image_size=image, batch_per_chip=2,
               num_classes=10)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, pool_batches=4)
    return cell
