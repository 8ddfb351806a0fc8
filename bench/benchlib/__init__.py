"""The on-chip benchmark's harness: one general runner driven by the data
files beside it (configurations, traffic mixes, limits, metric readers)."""
