SELECT l_shipmode, o_orderpriority, COUNT(1)
FROM lineitem JOIN orders ON lineitem.l_orderkey = orders.o_orderkey
WHERE (l_shipmode = '{mode1}' OR l_shipmode = '{mode2}')
  AND l_commitdate < l_receiptdate AND l_shipdate < l_commitdate
  AND l_receiptdate >= {date_lo} AND l_receiptdate < {date_hi}
GROUP BY l_shipmode, o_orderpriority
