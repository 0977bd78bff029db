"""Pairs (token, choice) on the busiest held expert over the mean of the
held experts: ServingEngine.stats()["experts"]["pairs_per_expert"], over
the engine's life in the job. 1 is an even load."""
from yardstick.kernel_readers import expert_load_max_over_mean as read  # noqa: F401
