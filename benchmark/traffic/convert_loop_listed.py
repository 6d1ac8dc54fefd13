"""Traffic kind ``convert_loop_listed``: ``convert_loop``'s closed loop,
unchanged, over ONE layer that a few huge files hold. The configuration lists
those files as they were measured (``listed_files``: bytes and kind, largest
first) and gives a law for the rest, the body (``file_law``); the layer is the
listed files as they stand plus ``image.image_shape``'s draw of the body for
the bytes that are left of ``measured.bytes``.

Why a kind of its own: ``image.image_shape`` draws every size from one
log-normal law, which cannot hold two shared objects of 614 and 307 MiB beside
2,925 files of median 5 KB, and a PR that is not a ``benchmark`` PR edits no
file that is here. Everything else is ``convert_loop``'s: the members'
bytes (``image.layer_bytes``: the files CDC cuts, every listed one among
them, from ``data_seed``; the others from ``--seed``), the tar order from
``--seed`` (``image.shuffled``), the tar (``image.write_tar``), the verbs and
the files a convert leaves.

A size follows ``image_mib``: every listed size is multiplied by ``image_mib``
÷ the layer's measured MiB (``round(measured.bytes ÷ 2**20)``), which is
exactly 1 at the committed size, and the body is drawn for what is then left
of ``measured.bytes`` times that factor. So the CPU rehearsal, which patches
``image_mib`` to a few MiB, converts the same layer in small.

Parameters of the cell's file as ``convert_loop``'s; ``image`` must be
"config" and ``dictionary`` null (a second image and a dictionary image would
each need their own list).
"""

from __future__ import annotations

import os
import time

from benchmark.traffic import image
from benchmark.traffic.convert_loop import SALT_CONFIG_IMAGE, ConvertLoop


def layer_members(config: dict) -> list[image.Member]:
    """The one layer's schema: the listed files, then the body's draw."""
    measured_bytes = config["measured"]["bytes"]
    factor = config["image_mib"] / round(measured_bytes / 2**20)
    listed = [image.Member(f"layer0/listed/f{i}.bin", int(f["bytes"] * factor), f["kind"])
              for i, f in enumerate(config["listed_files"])]
    left = round(measured_bytes * factor) - sum(m.size for m in listed)
    if left <= 0:
        raise SystemExit("benchmark: the listed files leave no bytes for the body")
    (body,) = image.image_shape(config["shape_seed"], config["file_law"], left, [1])
    return listed + body


class ConvertLoopListed(ConvertLoop):
    def generate(self) -> None:
        """The layer's tar into the work directory."""
        cfg, t0 = self.config, time.perf_counter()
        members = layer_members(cfg)
        datas = image.layer_bytes(self.seed, cfg["data_seed"], cfg["chunk_size"] // 4, SALT_CONFIG_IMAGE, 0, members)
        members, datas = image.shuffled(self.seed, SALT_CONFIG_IMAGE, 0, members, datas)
        self.tars.append(os.path.join(self.work, "layer0.tar"))
        self.tar_bytes.append(image.write_tar(self.tars[-1], members, datas))
        self.members.append(members)
        self.log("image", seed=self.seed, gen_s=time.perf_counter() - t0, layers=1, files=[len(members)],
                 tar_bytes=self.tar_bytes, listed_files=len(cfg["listed_files"]),
                 listed_bytes=sum(m.size for m in members if m.name.startswith("layer0/listed/")),
                 pooled_files=0, dictionary=False)


def build(cell: dict, config: dict, seed: int, work: str, log) -> ConvertLoopListed:
    if cell["image"] != "config" or cell.get("dictionary") is not None:
        raise SystemExit("benchmark: convert_loop_listed converts the configuration's one layer, with no dictionary")
    if config["layers"] != 1 or list(config["layer_weights"]) != [1]:
        raise SystemExit("benchmark: convert_loop_listed takes a configuration of one layer")
    return ConvertLoopListed(cell, config, seed, work, log)
