"""Shared settle executor: a device→host readback blocks its caller
until the device has finished (how long on the chip's own host: not
measured) but releases the GIL and parallelizes across threads — so
every session and pool settles results on this one pool of workers
instead of blocking the event loop."""

from concurrent.futures import ThreadPoolExecutor

SETTLE_POOL = ThreadPoolExecutor(max_workers=8, thread_name_prefix="swx-settle")

# query-path inference (REST forecasts, ad-hoc scoring) runs on its own
# small pool: a first-call model compile blocks its worker for as long
# as the compile takes and must never starve the scoring plane's settle
# pipeline above
QUERY_POOL = ThreadPoolExecutor(max_workers=2, thread_name_prefix="swx-query")
