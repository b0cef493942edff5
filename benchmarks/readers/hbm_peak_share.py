"""Peak bytes of live buffers on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``) plus the temporaries of the
largest program the run could ask (``memory_analysis()``; 0 where the
program does not hand its executables out), over ``bytes_limit``, %."""


def read(record, args):
    s = record.scalars
    if not s.get("bytes_limit"):
        return None
    return 100.0 * (s["live_peak_bytes"] + s.get("program_temp_bytes", 0)) \
        / s["bytes_limit"]
