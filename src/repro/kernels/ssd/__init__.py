from . import ref  # noqa: F401


def __getattr__(name):
    # the Pallas-backed op on first use: the prefill reads only ``ref``,
    # and importing Pallas takes seconds of a process's start
    if name == "ssd_scan":
        from .ops import ssd_scan
        return ssd_scan
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
