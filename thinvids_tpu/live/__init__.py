"""Live LL-HLS subsystem: encode while the source arrives, serve
viewers during ingest.

The batch ladder path (abr/) only produces output at job COMPLETION;
this package decouples output availability from job completion — the
low-latency-live model of JND-aware live-streaming encoding (PAPERS.md
arXiv:2401.15343) applied to the reference's watch-folder-as-ingest
design (SURVEY §2.4). `ingest/tail.py` follows a growing source
GOP-by-GOP, the executor's `_run_live` path feeds completed GOPs
through the existing ladder encoders wave-by-wave, and
:class:`LiveLadderPackager` here writes + announces each segment the
moment the GOP clears every rung: rolling live/EVENT playlists (no
EXT-X-ENDLIST until the stream closes), EXT-X-PART partial segments
with preload hints, and a sliding DVR window (EXT-X-MEDIA-SEQUENCE
advance + on-disk GC). The headline metric is glass-to-playlist
latency, not fps (not measured on the chip yet: ROADMAP S7).
"""

from .packager import LiveLadderPackager

__all__ = ["LiveLadderPackager"]
