"""Router: live (token, choice) rows routed to experts this chip does not
hold, over the live rows routed, in the window's decode steps (padding
rows, an idle slot's or a bucket's, are not counted). A count with a
value it should have: the share of the router's experts that live
elsewhere (50% where 64 of 128 are held), or the share the layer was told
it holds is wrong. `better` says lower only because an entry must say
one: fewer absent rows are fewer rows for the other chip to send back."""

from benchmarks.lib import mla_readers


def read(run):
    return mla_readers.experts_absent_share(run)
