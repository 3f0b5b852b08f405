"""Journal commits (``Journal.commits``) per fs operation in the window."""

from benchkit.readers import ratio


def read(record):
    return ratio(record, "fs", "journal_commits", "ops")
