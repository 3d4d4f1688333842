"""The benchmark (ISSUE 25): see README.md."""
