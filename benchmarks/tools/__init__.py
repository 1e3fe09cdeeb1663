"""Tools around the harness: studies and controls run by hand, never by a run."""
