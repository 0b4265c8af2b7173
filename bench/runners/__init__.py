"""One runner per traffic ``runner`` kind; each defines ``Run``."""
