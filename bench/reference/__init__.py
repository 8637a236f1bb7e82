"""Plain float32 references for the benchmark's correctness check. They
import nothing of the program under test."""
