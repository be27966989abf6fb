"""Process start to the first submit of the window: daemon start, device
attach, native packer build if absent, source generation, compile or
cache load, one warm-up job, the XLA mirror child."""


def read(ev):
    return ev["setup_s"]
