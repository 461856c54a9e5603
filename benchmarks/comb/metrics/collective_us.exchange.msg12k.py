"""As ``collective_us.exchange``, in the four-chip cell at 98,304 B faces,
which reports ``exchange_us``."""

from benchmarks.comb import spec

read = spec.load_reader("collective_us.exchange")
