"""The benchmark's own plain references (numpy; nothing of the program)."""
