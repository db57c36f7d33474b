"""Entry: serve call -> first ``on_token`` of the burst, median over the
window's bursts (the per-call re-trace of the loop's jitted lambdas, the
``PagedKV`` allocation and the first prefill are all inside). Host clock."""
import statistics


def read(run):
    firsts = [min(t for t in b.log.ttft_s() if t is not None)
              for b in run["bursts"]]
    return 1e3 * statistics.median(firsts)
