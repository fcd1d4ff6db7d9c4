"""The window's arithmetic over the client's records."""
from types import SimpleNamespace

from benchlib.flops import DenseDims, prefill_flops
from benchlib.window import Run, bursts, cached_tokens, window_flops

DIMS = DenseDims(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16,
                 d_ff=128, vocab=256)


def _rec(prompt, times, cached=None):
    result = (None if cached is None
              else SimpleNamespace(cached_prefix_tokens=cached))
    return SimpleNamespace(prompt=prompt, times=times, result=result)


def _run(records, warm=(), seconds=10.0):
    return Run(cell=None, dims=DIMS, peaks=None, chips=1, seconds=seconds,
               setup_s=1.0, t0=100.0, st0={}, st1={"page_size": 16},
               records=records, warm_prompts=list(warm))


def test_cancelled_record_counts_only_uncached_tokens():
    """A request the window's close cancelled has no result: its cached
    prefix is the longest page-aligned one an earlier prompt shares."""
    head = list(range(70))                 # shared: 64 tokens page aligned
    done = _rec(head + [1, 2, 3], [100.1, 100.2], cached=64)
    cancelled = _rec(head + [5] * 20, [100.3])
    repeat = _rec(head + [5] * 20, [100.35])      # the same prompt again
    cold = _rec([7] * 30, [100.4])
    before = _rec(list(range(40)), [99.0], cached=0)   # prefill before t0
    run = _run([before, done, cancelled, repeat, cold],
               warm=[head + [900, 901]])
    assert cached_tokens(run) == [0, 64, 64, 90, 0]
    pre, _ = window_flops(run)
    assert pre == (prefill_flops(DIMS, 73, 64) + prefill_flops(DIMS, 90, 64)
                   + prefill_flops(DIMS, 90, 90)
                   + prefill_flops(DIMS, 30, 0))


def test_bursts_per_token():
    """Two chunks of 4 tokens 0.2 s apart: 50 ms per token; tokens outside
    the window are left out."""
    times = [100.0 + i * 1e-4 for i in range(4)] + \
        [100.2 + i * 1e-4 for i in range(4)] + [111.0]
    run = _run([_rec([1, 2], times, cached=0)])
    assert len(bursts(run)) == 1
    assert abs(bursts(run)[0] - 50.0) < 1e-6
