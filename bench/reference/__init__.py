"""Plain references the benchmark compares the timed path against. They
import nothing of the program under test."""
