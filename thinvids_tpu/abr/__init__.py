"""ABR ladder subsystem: device-side downscale, multi-rendition encode,
HLS packaging.

Three pieces, split along the jax boundary:

- :mod:`.scale` — jittable separable polyphase Lanczos-3 downscaler.
  Taps precompute on host as two small resampling matrices per plane;
  the device applies them as two matmuls, so every lower ladder rung is
  derived from the ALREADY-STAGED wave tensors (decode + H2D happens
  once per wave regardless of rung count — proven by the `h2d_bytes`
  stage counter).
- :mod:`.ladder` — rung planner (source → e.g. 1080/720/480/360 with
  per-rung QPs from the R ∝ 2^(−qp/6) rate model) and
  :class:`~.ladder.LadderShardEncoder`, the multi-rendition encoder the
  executors drive. jax-free at module scope.
- :mod:`.hls` — closed-GOP-aligned fMP4 segmenter + media/master
  playlist writer + conformance lint. jax-free entirely, so packaging
  runs on worker/sidecar processes that never load a device backend.

This package intentionally has NO module-scope imports: `ladder` and
`hls` must stay importable on jax-free processes, and importing `scale`
here would drag jax into both.
"""
