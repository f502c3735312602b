"""p90 of the time to first token, in ms, over the window's requests (the
end-to-end ``ttft_p50_ms``'s tail).  Below the knee it is the wait for a
free slot under each run's bursts of arrivals, which swings from run to
run, so it is read here, without a bound, and not as an end-to-end
metric."""


def read(run):
    return run.e2e.get("ttft_p90_ms")
