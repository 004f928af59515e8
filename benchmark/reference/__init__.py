"""Plain references: the reader, and one model module per configuration."""
