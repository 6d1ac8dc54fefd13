"""Converter option surfaces — semantic parity with reference types.go:58-145."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

from nydus_snapshotter_tpu import constants
from nydus_snapshotter_tpu.models import layout


class ConvertError(RuntimeError):
    pass


@dataclass
class PackOption:
    """Options for packing one OCI layer tar into a nydus blob.

    Field semantics follow reference PackOption (pkg/converter/types.go:58-90);
    fields that configured the external builder binary are replaced by engine
    selection knobs (``backend``, ``chunking``).
    """

    work_dir: str = ""
    fs_version: str = layout.RAFS_V6
    chunk_dict_path: str = ""
    prefetch_patterns: str = ""
    # lz4_block matches the legacy v5 blob default; modern nydus-image
    # defaults chunk compression to zstd. We default to lz4_block as a
    # deliberate speed-over-ratio choice (zstd opts into better ratio at
    # ~2x the pack cost).
    compressor: str = "lz4_block"  # "none" | "zstd" | "lz4_block"
    # LZ4 acceleration (liblz4 LZ4_compress_fast): 1 = default-codec
    # output (max ratio); each step up trades ratio for speed (~linear).
    # Deterministic for a fixed value, so parallel/serial/native arms all
    # produce identical bytes.
    lz4_acceleration: int = 1
    oci_ref: bool = False
    aligned_chunk: bool = False
    chunk_size: int = constants.CHUNK_SIZE_DEFAULT
    batch_size: int = 0
    timeout: Optional[float] = None
    encrypt: bool = False
    # Engine selection (replaces BuilderPath): hybrid = the fused native
    # host arm (SIMD bitmaps + SHA-NI) — the default, like the reference
    # defaulting to its production builder; jax = force the TPU batch arm;
    # fused = the device lane (ops/fused_convert); numpy = host
    # differential path.
    backend: str = "hybrid"
    chunking: str = "cdc"  # "cdc" | "fixed"
    # "" = engine default for the backend; "jax" routes chunk digests
    # through the device batch path while boundaries stay on the host
    # (bench.py's device_digest arm).
    digest_backend: str = ""
    # Chunk-digest algorithm (reference `nydus-image --digester`,
    # RafsSuperFlags 0x4 blake3 / 0x8 sha256). blake3 is the real
    # toolchain's default — packing with it makes `--chunk-dict
    # bootstrap=<real nydus image>` content hits possible, since dict
    # probes are digest-keyed. The blob ID stays sha256 (OCI convention).
    # sha256 keeps the SHA-NI/device fused fast paths; blake3 digests run
    # on the host blake3 arm (native ntpu_blake3_many or pure Python).
    digester: str = "sha256"

    def validate(self) -> None:
        if self.fs_version not in (layout.RAFS_V5, layout.RAFS_V6):
            raise ConvertError(f"invalid fs version {self.fs_version!r}")
        if self.compressor not in ("none", "zstd", "lz4_block"):
            raise ConvertError(f"unsupported compressor {self.compressor!r}")
        if not 1 <= self.lz4_acceleration <= 65537:
            raise ConvertError(
                f"lz4 acceleration {self.lz4_acceleration} out of range [1, 65537]"
            )
        cs = self.chunk_size
        if cs & (cs - 1) or not (constants.CHUNK_SIZE_MIN <= cs <= constants.CHUNK_SIZE_MAX):
            raise ConvertError(
                f"chunk size must be power of two in "
                f"[{constants.CHUNK_SIZE_MIN:#x}, {constants.CHUNK_SIZE_MAX:#x}]"
            )
        if self.digest_backend not in ("", "host", "jax"):
            raise ConvertError(
                f"unsupported digest backend {self.digest_backend!r}"
            )
        if self.digester not in ("sha256", "blake3"):
            raise ConvertError(f"unsupported digester {self.digester!r}")
        bs = self.batch_size
        # Reference bound (types.go:78-79): power of two in 0x1000-0x1000000
        # or zero (disabled).
        if bs and (
            bs & (bs - 1) or not (constants.CHUNK_SIZE_MIN <= bs <= constants.CHUNK_SIZE_MAX)
        ):
            raise ConvertError(
                f"batch size must be zero or a power of two in "
                f"[{constants.CHUNK_SIZE_MIN:#x}, {constants.CHUNK_SIZE_MAX:#x}]"
            )


@dataclass
class MergeOption:
    """Options for merging layer bootstraps into an image bootstrap
    (reference types.go:92-133)."""

    work_dir: str = ""
    # Empty = inherit the version of the top layer (explicit value overrides).
    fs_version: str = ""
    chunk_dict_path: str = ""
    parent_bootstrap_path: str = ""
    prefetch_patterns: str = ""
    with_tar: bool = False
    oci: bool = False
    oci_ref: bool = False
    with_referrer: bool = False
    timeout: Optional[float] = None
    # "native" (this framework's format), or the reference toolchain's
    # real on-disk layouts: "rafs-v5" / "rafs-v6" (models/nydus_real_write).
    bootstrap_format: str = "native"
    # Inode-digest algorithm when emitting a real layout ("blake3" is the
    # toolchain default; use the same algorithm the layers' CHUNK digests
    # were packed with — PackOption.digester — for a coherent image).
    digester: str = "sha256"


@dataclass
class UnpackOption:
    """Options for unpacking a nydus blob back to an OCI tar
    (reference types.go:135-145)."""

    work_dir: str = ""
    timeout: Optional[float] = None
    stream: bool = False
