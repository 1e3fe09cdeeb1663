"""Data made from --seed (numpy only; nothing of the program)."""
