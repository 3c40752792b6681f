"""plan_host_s.oneshot: host seconds per product in ``spgemm.plan`` spans,
read as ``plan_host_s.product``; in this cell every call misses the
``PlanCache``, so each span holds the fingerprint, Alg. 1 and the Table-I
binning."""

from workload import load

read = load("metrics", "plan_host_s.product").read
