"""TiTPU's benchmark: the yardstick later PRs are measured with."""
