"""Shared parts of the benchmark: the yardstick that later PRs may not edit."""
