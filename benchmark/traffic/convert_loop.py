"""Traffic kind ``convert_loop``: one client converts one image again and
again — ``pack`` each layer, then ``merge`` — and sends the next verb only
when the last has answered (closed loop, as a registry's convert-on-push
worker runs). Parameters come from the cell's file:

    image       "config" (the configuration's image) or
                {"mib", "layer_weights", "reuse_fraction"}: a second image,
                that share of whose files are the dictionary image's
    dictionary  null, or "config": the configuration's image is converted in
                set-up on the host lane, and every pack of the window names
                its merged bootstrap as --chunk-dict
"""

from __future__ import annotations

import os
import time

from benchmark import program
from benchmark.traffic import image

SALT_CONFIG_IMAGE, SALT_SECOND_IMAGE = 1, 2


class ConvertLoop:
    def __init__(self, cell: dict, config: dict, seed: int, work: str, log):
        self.cell, self.config, self.seed, self.work, self.log = cell, config, seed, work, log
        self.pack_args = list(config["pack_args"])
        self.tars: list[str] = []
        self.tar_bytes: list[int] = []
        self.members: list[list[image.Member]] = []
        self.dict_boot = ""
        self.dict_files: list = []  # the dictionary image's files, for the plain reference
        self.fused_packs = 0  # every pack sent to the device lane, set-up's too

    # -- set-up ---------------------------------------------------------------

    def generate(self) -> None:
        """Tars into the work directory; with a dictionary, its bootstrap too."""
        cfg, law, t0 = self.config, self.config["file_law"], time.perf_counter()
        shape = image.image_shape(cfg["shape_seed"], law, cfg["image_mib"] << 20, cfg["layer_weights"])
        drawn = (self.seed, cfg["data_seed"], cfg["chunk_size"] // 4)  # CDC leaves files <= avg/4 whole
        spec, pool = self.cell["image"], None
        if self.cell.get("dictionary") == "config":
            pool, dict_tars = [], []
            for li, members in enumerate(shape):
                datas = image.layer_bytes(*drawn, SALT_CONFIG_IMAGE, li, members)
                dict_tars.append(os.path.join(self.work, f"dict{li}.tar"))
                image.write_tar(dict_tars[-1], *image.shuffled(self.seed, SALT_CONFIG_IMAGE, li, members, datas))
                pool += datas
            self.dict_boot, self.dict_files = self._host_convert(dict_tars, "dict"), pool
        if spec == "config":
            salt = SALT_CONFIG_IMAGE
        else:
            salt = SALT_SECOND_IMAGE
            shape = image.image_shape(cfg["shape_seed"] + 1, law, spec["mib"] << 20, spec["layer_weights"],
                                      [len(d) for d in pool], spec["reuse_fraction"])
        for li, members in enumerate(shape):
            datas = image.layer_bytes(*drawn, salt, li, members, pool)
            members, datas = image.shuffled(self.seed, salt, li, members, datas)
            self.tars.append(os.path.join(self.work, f"layer{li}.tar"))
            self.tar_bytes.append(image.write_tar(self.tars[-1], members, datas))
            self.members.append(members)
        self.log("image", seed=self.seed, gen_s=time.perf_counter() - t0, layers=len(self.tars),
                 files=[len(m) for m in self.members], tar_bytes=self.tar_bytes,
                 pooled_files=sum(m.kind == "pooled" for ms in self.members for m in ms),
                 dictionary=bool(self.dict_boot))

    def _host_convert(self, tars: list[str], tag: str) -> str:
        """The dictionary image through the host lane (no device program)."""
        t0, blobs = time.perf_counter(), []
        for i, tar in enumerate(tars):
            blobs.append(os.path.join(self.work, f"{tag}{i}.nydus"))
            program.cli(["pack", "--in", tar, "--out", blobs[-1], *self._args("hybrid")])
        boot = os.path.join(self.work, f"{tag}.boot")
        program.cli(["merge", "--out", boot, *blobs])
        for path in blobs + tars:
            os.unlink(path)
        self.log("dictionary", layers=len(tars), wall_s=time.perf_counter() - t0)
        return boot

    def _args(self, backend: str, extra=()) -> list[str]:
        args = list(self.pack_args)
        args[args.index("--backend") + 1] = backend
        return args + list(extra)

    # -- the verbs --------------------------------------------------------------

    def verbs(self, out_dir: str, backend: str = "", extra=(), use_dict: bool = True):
        """One whole convert as (verb, layer, tar bytes, argv) in order.
        ``backend``/``extra``/``use_dict`` are for the reference and the control."""
        args = self._args(backend, extra) if backend else list(self.pack_args) + list(extra)
        if self.dict_boot and use_dict:
            args += ["--chunk-dict", self.dict_boot]
        blobs = []
        for li, tar in enumerate(self.tars):
            blobs.append(os.path.join(out_dir, f"layer{li}.nydus"))
            yield "pack", li, self.tar_bytes[li], ["pack", "--in", tar, "--out", blobs[-1], *args]
        yield "merge", -1, 0, ["merge", "--out", os.path.join(out_dir, "image.boot"), *blobs]

    def files(self) -> list[str]:
        """What one whole convert leaves in its directory."""
        return [f"layer{li}.nydus" for li in range(len(self.tars))] + ["image.boot"]


def build(cell: dict, config: dict, seed: int, work: str, log) -> ConvertLoop:
    return ConvertLoop(cell, config, seed, work, log)
