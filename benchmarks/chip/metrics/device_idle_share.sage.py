"""device_idle_share.sage: the share of the traced window in which no
operation ran on the device, in percent, read as
``device_idle_share.train``."""

from workload import load

read = load("metrics", "device_idle_share.train").read
