"""Seconds of the plan build in set-up (host: ordering, symbolic
factorization, frontal maps), from the benchmark's span around
`plan_factorization`.  A served cell's plan is built inside
`prefactor` and has no span of its own, so nothing is read there."""


def read(run):
    return run.spans.total("bench.plan")
