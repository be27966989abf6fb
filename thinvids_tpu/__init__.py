"""thinvids_tpu — a TPU-native distributed video transcoding framework.

A ground-up rebuild of the capabilities of AwsGeek/thinvids (a Redis/Huey/
ffmpeg/VAAPI thin-client transcoding farm) designed TPU-first:

- the encode path is jitted JAX compute (integer transforms, quantization,
  intra prediction, fused motion search + compensation) over HBM-resident
  YUV planes plus a native C++ CAVLC entropy packer, instead of external
  ffmpeg+VAAPI processes;
- segment/GOP parallelism uses ``jax.sharding.Mesh`` + ``shard_map``
  (closed GOPs fanned over devices per wave, two-tier sparse level
  transfer back to host) instead of Huey task dispatch to worker nodes;
- rate control is collective: per-GOP complexity stats are exchanged with
  ``jax.lax.psum`` over the mesh inside the sharded program, feeding a
  two-pass VBR QP solve (parallel/rc.py);
- the control plane (durable journal-backed job store, scheduler,
  watchdog, heartbeats, activity log, executor with per-wave retry) is a
  coordinator whose semantics port the reference's manager, fronted by a
  stdlib HTTP JSON API + single-page dashboard.

Layout:
    core/      video types, layered config, status/events, logging, devices
    codecs/    H.264 intra+inter encode (JAX compute, bit-exact vs
               libavcodec) + CAVLC entropy coding
    parallel/  segment planner, mesh helpers, shard_map GOP dispatch,
               psum rate control
    cluster/   coordinator, durable job store, admission policy, executor,
               node agent (host + HBM metrics), remote worker backend
               (HTTP shard board + worker daemon, cluster/remote.py)
    ingest/    watch-folder discovery + processed ledger, native probe,
               input decode (.y4m, .mp4/AVC via bound libavcodec)
    io/        y4m reader/writer, bit writer, MP4 muxer/demuxer with
               audio-track passthrough
    api/       HTTP JSON API over the coordinator (reference route set)
    ui/        static dashboard page served at / by the API
    tools/     libavcodec ctypes oracle, PSNR/SSIM metrics, stamp/seam
               watermark harness
    native/    C++ hot paths (CAVLC entropy packing) loaded via ctypes
    cli.py     coordinator + agent + worker daemon entrypoints
               (deploy/*.service)

H.264 in-loop deblocking (§8.7, in the order §8.7 prescribes: what any
decoder reconstructs, sample for sample) runs on the recon carried
between frames (codecs/h264/deblock.py, jaxdeblock.py) and is signaled
in the slice headers; like the other rate-distortion features it is off
by default (the `deblock` setting, core/config.py).
"""

__version__ = "0.4.0"
