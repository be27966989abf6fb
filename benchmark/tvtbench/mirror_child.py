"""CPU child: the IDR and the first P frame of a source, encoded by the
program's XLA mirror with the job's own settings.

    JAX_PLATFORMS=cpu python mirror_child.py SOURCE OUT BANDS

The design's claim is that the Pallas kernel on the chip, its XLA
mirror and the host packer are integer-exact, so these slice NAL units
must equal the first ones of the chip's output byte for byte. This is
the only file of the benchmark that imports the program (after
`chip_smoke.cpu_leg`); it runs beside the daemon's start-up and has
ended before the window opens. Writes the slice NALs (types 1 and 5)
4-byte length-prefixed to OUT."""

import os
import struct
import sys

FRAMES = 2


def main(source_path, out_path, bands):
    from thinvids_tpu.core.devices import (configure_compile_cache,
                                           force_cpu_devices)

    force_cpu_devices(max(1, bands))
    configure_compile_cache()

    import jax

    from thinvids_tpu.core.config import get_settings, overlay_job_settings
    from thinvids_tpu.core.types import (GopSpec, SegmentPlan,
                                         concat_segments)
    from thinvids_tpu.ingest.decode import open_video
    from thinvids_tpu.io.mp4 import split_annexb
    from thinvids_tpu.parallel.dispatch import (default_mesh,
                                                make_shard_encoder)

    if jax.default_backend() != "cpu":
        raise SystemExit(f"mirror child on {jax.default_backend()!r}")
    settings = overlay_job_settings(
        get_settings(), {"sfe_bands": bands} if bands else {})
    with open_video(source_path) as source:
        mesh = default_mesh(jax.devices()[:max(1, bands)])
        enc = make_shard_encoder(source.meta, settings, mesh)
        enc.plan_override = SegmentPlan(
            gops=(GopSpec(index=0, start_frame=0, num_frames=FRAMES),),
            num_devices=enc.num_devices,
            frames_per_gop=int(settings.gop_frames))
        nals = split_annexb(concat_segments(enc.encode(source[0:FRAMES])))
    with open(out_path + ".tmp", "wb") as fp:
        for nal in nals:
            if nal and (nal[0] & 0x1F) in (1, 5):
                fp.write(struct.pack(">I", len(nal)) + nal)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2], int(sys.argv[3])))
